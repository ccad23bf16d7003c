"""How an update is applied inside the compiled step: the optimizer
kernels (fp32 math on the parameter's persistent store), one write-back
for a whole store or for the live rows of a RowSparse table, and the
non-finite guard's gate over everything a step writes.

These kernels are the step's own; ``optimizer/optimizer.py`` and
``ops/optimizer_ops.py`` hold the Trainer's (ROADMAP D7: merging them
changes summation order).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def _sgd_init(p):
    return (jnp.zeros_like(p),)


def _sgd_update(p, g, s, lr, momentum=0.9, wd=0.0):
    mom, = s
    g = g + wd * p
    new_mom = momentum * mom - lr * g
    return p + new_mom, (new_mom,)


def _adam_init(p):
    return (jnp.zeros_like(p), jnp.zeros_like(p), jnp.zeros((), jnp.int32))


def _adam_update(p, g, s, lr, beta1=0.9, beta2=0.999, eps=1e-8, wd=0.0):
    m, v, t = s
    t = t + 1
    g = g + wd * p
    m = beta1 * m + (1 - beta1) * g
    v = beta2 * v + (1 - beta2) * jnp.square(g)
    mhat = m / (1 - beta1 ** t.astype(jnp.float32))
    vhat = v / (1 - beta2 ** t.astype(jnp.float32))
    return p - lr * mhat / (jnp.sqrt(vhat) + eps), (m, v, t)


def _adamw_update(p, g, s, lr, beta1=0.9, beta2=0.999, eps=1e-8, wd=0.01,
                  eta=1.0):
    # reference semantics (src/operator/contrib/adamw.cc, the GluonNLP
    # BERTAdam recipe): NO bias correction, decoupled wd scaled by lr —
    # kept identical to ops/optimizer_ops.py adamw_update so the Trainer
    # and ShardedTrainStep paths produce the same trajectory
    # (tests/test_gradients.py parity check)
    m, v, t = s
    t = t + 1
    m = beta1 * m + (1 - beta1) * g
    v = beta2 * v + (1 - beta2) * jnp.square(g)
    return p - eta * (lr * m / (jnp.sqrt(v) + eps) + wd * lr * p), \
        (m, v, t)


def _lamb_update(p, g, s, lr, beta1=0.9, beta2=0.999, eps=1e-6, wd=0.01):
    m, v, t = s
    t = t + 1
    m = beta1 * m + (1 - beta1) * g
    v = beta2 * v + (1 - beta2) * jnp.square(g)
    mhat = m / (1 - beta1 ** t.astype(jnp.float32))
    vhat = v / (1 - beta2 ** t.astype(jnp.float32))
    update = mhat / (jnp.sqrt(vhat) + eps) + wd * p
    r1 = jnp.linalg.norm(p.reshape(-1))
    r2 = jnp.linalg.norm(update.reshape(-1))
    ratio = jnp.where((r1 > 0) & (r2 > 0), r1 / r2, 1.0)
    return p - lr * ratio * update, (m, v, t)


_OPTS = {
    'sgd': (_sgd_init, _sgd_update),
    'adam': (_adam_init, _adam_update),
    'adamw': (_adam_init, _adamw_update),
    'lamb': (_adam_init, _lamb_update),
}


# the dense update: every row of the store, replaced in place
WHOLE = (lambda a: a, lambda _a, new: new)


def row_access(uids, dim, flat=None):
    """(get, set) over the rows ``uids`` of a table-shaped store, or of
    a ZeRO-3 flat padded store (``flat``), where a row is a contiguous
    dim-slice of the 1-D buffer. Sentinel slots (uid == vocab) gather a
    clipped garbage row whose writeback XLA's OOB scatter DROPS — dead
    slots never touch the table."""
    if flat is not None:
        idx = (uids[:, None] * dim + jnp.arange(
            dim, dtype=jnp.int32)[None, :])
        return (lambda a: jnp.take(a, idx, mode='clip'),
                lambda a, r: a.at[idx].set(r, mode='drop'))
    return (lambda a: jnp.take(a, uids, axis=0, mode='clip'),
            lambda a, r: a.at[uids].set(r, mode='drop'))


def apply(opt_update, opt_kwargs, lr, param, master, state, grad, shape,
          access=WHOLE, flat=None, constraint=None):
    """One parameter's update: (new param, new master or None, new state).

    The fp32 value is ``master`` where the parameter keeps one, else the
    parameter cast up (and laid out as ``constraint`` says, the layout its
    sharded gradient arrives in). ``access`` selects what the kernel sees
    and writes: ``WHOLE``, or the live rows of a RowSparse table (the
    reference's lazy_update=True / kvstore row_sparse semantics: the SAME
    kernel on the (budget, dim) block of value and moments, scattered
    back; moments of absent rows stay frozen; wd applies to live rows
    only). A ``flat`` parameter's fp32 store is its master; the
    replicated logical compute-dtype copy is refreshed from it (slice
    off the pad)."""
    get, put = access
    if master is not None:
        p32 = master
    else:
        p32 = param.astype(jnp.float32)
        if constraint is not None:
            p32 = jax.lax.with_sharding_constraint(p32, constraint)
    new_rows, new_state_rows = opt_update(
        get(p32), grad, tuple(get(s) if s.ndim else s for s in state),
        lr, **opt_kwargs)
    new32 = put(p32, new_rows)
    new_state = tuple(put(s, sr) if s.ndim else sr
                      for s, sr in zip(state, new_state_rows))
    if flat is not None:
        return new32[:flat['size']].reshape(shape).astype(param.dtype), \
            new32, new_state
    return new32.astype(param.dtype), \
        (new32 if master is not None else None), new_state


def gate_writeback(ok, new, old):
    """The non-finite guard fused into the step: a bad step writes back
    the OLD params/frozen/master/state/residual on device — a no-op
    update inside the same XLA program, no host round-trip on the happy
    path. The residual writeback is gated too: a NaN residual must never
    outlive the skipped step that produced it. ``new`` and ``old`` are
    (params, frozen, master, state, residual)."""
    new_params, new_f, new_master, new_state, new_residual = new
    params, f_params, master, state, residual = old
    new_params = {n: jnp.where(ok, v, params[n])
                  for n, v in new_params.items()}
    new_master = {n: jnp.where(ok, v, master[n])
                  for n, v in new_master.items()}
    new_state = {n: tuple(jnp.where(ok, ns_, os_)
                          for ns_, os_ in zip(v, state[n]))
                 for n, v in new_state.items()}
    new_residual = {n: jnp.where(ok, v, residual[n])
                    for n, v in new_residual.items()}
    new_f = {n: jnp.where(ok, v, f_params[n]) for n, v in new_f.items()}
    return new_params, new_f, new_master, new_state, new_residual

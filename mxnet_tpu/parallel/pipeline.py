"""Pipeline parallelism: GPipe-style microbatch schedule over a mesh
'pp' axis.

BEYOND the reference: MXNet's model parallelism is manual layer placement
(`Module(group2ctxs=...)`, src/operator/cross_device_copy.cc) with no
pipeline schedule (SURVEY §2.5 "no GPipe/1F1B anywhere"). Here pipeline
stages are a first-class mesh axis: every device holds ONE stage's
parameters (stacked leaves sharded over 'pp'), microbatches stream
through the ring with `lax.ppermute` on ICI neighbor links, and the whole
schedule — forward bubbles, steady state, drain — is a single `lax.scan`
inside `shard_map`, so XLA sees one static program and autodiff runs
straight through the collectives (GPipe: Huang et al. 2019; the ppermute
ring mirrors the ring-attention pattern in ring_attention.py).

Design notes (TPU-first):
- SPMD, not MPMD: all stages run the same `stage_fn`; heterogeneous
  models are expressed by stacking per-stage parameters (vmap-style),
  exactly how scan-over-layers works in JAX transformer stacks.
- The schedule runs S + M - 1 ticks for S stages / M microbatches.
  Devices idle in the bubble ticks compute garbage that is masked out —
  branchless, static shapes, no host control flow.
- Gradients: `jax.grad` differentiates through the scan + ppermute
  (transpose of ppermute is the reverse permute), yielding the standard
  GPipe backward schedule without writing it by hand.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

__all__ = ['pipeline_forward', 'pipeline_loss_fn', 'stack_stage_params',
           'split_layers_into_stages', 'pipeline_composite_loss',
           'PipelineTrainStep']


def stack_stage_params(stage_param_list):
    """Stack a list of per-stage parameter pytrees (identical structure)
    into one pytree whose leaves gain a leading stage axis — shard that
    axis over 'pp' and each device holds exactly its stage's weights."""
    return jax.tree_util.tree_map(
        lambda *xs: jnp.stack(xs, axis=0), *stage_param_list)


def split_layers_into_stages(layer_params, n_stages):
    """Group a list of per-layer pytrees into n_stages stacked groups:
    [L0..L3] with 2 stages -> stage leaf shape (2, 2, ...) where
    leading axis is stage, second is layer-within-stage."""
    n = len(layer_params)
    assert n % n_stages == 0, (n, n_stages)
    per = n // n_stages
    stages = []
    for s in range(n_stages):
        stages.append(jax.tree_util.tree_map(
            lambda *xs: jnp.stack(xs, axis=0),
            *layer_params[s * per:(s + 1) * per]))
    return stack_stage_params(stages)


def pipeline_forward(stage_fn, stage_params, x_microbatches, mesh,
                     pp_axis='pp'):
    """Run microbatches through the stage pipeline.

    stage_fn(params_one_stage, x) -> y: one stage's computation; applied
    by every device to its resident stage. With grouped layers, make
    stage_fn itself a lax.scan over the layer axis.
    stage_params: pytree with leading stage axis (see stack_stage_params),
    sharded over pp_axis.
    x_microbatches: (M, mb, ...) microbatches, replicated.
    Returns (M, mb, ...) outputs of the LAST stage (replicated — each
    bubble tick's garbage is dropped on the floor and outputs psum-
    broadcast from the last stage).
    """
    S = mesh.shape[pp_axis]
    M = x_microbatches.shape[0]
    n_ticks = S + M - 1

    def spmd(params, xs):
        # params: this device's stage (leading axis stripped by shard_map
        # to size 1) — drop it
        params = jax.tree_util.tree_map(lambda p: p[0], params)
        stage = lax.axis_index(pp_axis)
        fwd_perm = [(i, (i + 1) % S) for i in range(S)]

        def tick(carry, t):
            held, outs = carry
            # stage 0 injects microbatch t (clamped; bubble ticks recompute
            # an already-sent microbatch and the result is masked later)
            inject = xs[jnp.clip(t, 0, M - 1)]
            cur = jnp.where(stage == 0, inject, held)
            y = stage_fn(params, cur)
            # last stage emits microbatch m = t - (S - 1) at tick t
            m = t - (S - 1)
            is_out = (stage == S - 1) & (m >= 0)
            outs = lax.cond(
                m >= 0,
                lambda o: o.at[jnp.clip(m, 0, M - 1)].set(
                    jnp.where(is_out, y, o[jnp.clip(m, 0, M - 1)])),
                lambda o: o,
                outs)
            # rotate activations one stage forward
            held = lax.ppermute(y, pp_axis, fwd_perm)
            return (held, outs), None

        held0 = jnp.zeros_like(stage_fn(params, xs[0]))
        outs0 = jnp.zeros((M,) + held0.shape, held0.dtype)
        (_, outs), _ = lax.scan(tick, (held0, outs0),
                                jnp.arange(n_ticks))
        # broadcast the last stage's collected outputs to all devices
        # (psum works because every other stage contributes zeros)
        outs = jnp.where(stage == S - 1, outs, jnp.zeros_like(outs))
        return lax.psum(outs, pp_axis)

    pp_spec = P(pp_axis)
    in_specs = (jax.tree_util.tree_map(lambda _: pp_spec, stage_params),
                P())
    mapped = shard_map(spmd, mesh=mesh, in_specs=in_specs,
                       out_specs=P(), check_vma=False)
    return mapped(stage_params, x_microbatches)


def pipeline_loss_fn(stage_fn, loss_fn, mesh, pp_axis='pp'):
    """Build loss(stage_params, x_microbatches, y_microbatches) -> scalar
    running the pipeline forward and averaging per-microbatch losses.
    Differentiable: jax.grad through the scan/ppermute yields the GPipe
    backward schedule."""

    def loss(stage_params, x_mb, y_mb):
        out = pipeline_forward(stage_fn, stage_params, x_mb, mesh,
                               pp_axis=pp_axis)
        return jnp.mean(jax.vmap(loss_fn)(out, y_mb))

    return loss


# ---------------------------------------------------------------------------
# Heterogeneous models (VERDICT r4 #6): real networks are not a uniform
# layer stack — BERT is embedding → N identical encoder layers → task
# head. The pipeline axis carries the encoder (where the FLOPs are);
# embedding and head run replicated on every device outside the scan.
# That is the standard TPU GPipe layout: embed/head are O(vocab·C) per
# microbatch — negligible next to the encoder — and replicating them
# avoids both pipeline bubbles for tiny stages and pytree-heterogeneity
# inside the scan carry.
# ---------------------------------------------------------------------------

def pipeline_composite_loss(embed_fn, stage_fn, head_fn, loss_fn, mesh,
                            pp_axis='pp'):
    """loss(params, x_mb, y_mb) -> scalar for an embed→stages→head model.

    params: {'embed': pytree, 'stages': stacked pytree (leading stage
    axis, shard over pp), 'head': pytree}.
    embed_fn(embed_params, x) -> h; stage_fn(one_stage_params, h) -> h;
    head_fn(head_params, h) -> outputs (any pytree); loss_fn(outputs, y)
    -> scalar. x_mb / y_mb are pytrees with a leading (M, mb) microbatch
    axis on every leaf.
    """
    def loss(params, x_mb, y_mb):
        h = jax.vmap(lambda x: embed_fn(params['embed'], x))(x_mb)
        out = pipeline_forward(stage_fn, params['stages'], h, mesh,
                               pp_axis=pp_axis)
        per_mb = jax.vmap(
            lambda o, y: loss_fn(head_fn(params['head'], o), y))(out, y_mb)
        return jnp.mean(per_mb)

    return loss


class PipelineTrainStep:
    """Compiled fwd+bwd+update training step over a 'pp' mesh axis — the
    public pipeline entry point (beyond reference: SURVEY §2.5 lists no
    pipeline schedule; the reference's model parallelism is manual
    placement, python/mxnet/module/module.py group2ctxs).

    Usage:
        step = PipelineTrainStep(params, embed_fn, stage_fn, head_fn,
                                 loss_fn, 'adamw', {'learning_rate': 1e-3},
                                 mesh=mesh)
        loss = step(x_mb, y_mb)   # microbatched pytrees; params updated

    Stage parameters live sharded over pp (each device holds only its
    stage); embed/head replicate. The whole step is ONE jit program with
    donated param/opt-state buffers, mirroring ShardedTrainStep.
    """

    def __init__(self, params, embed_fn, stage_fn, head_fn, loss_fn,
                 optimizer='sgd', optimizer_params=None, mesh=None,
                 pp_axis='pp'):
        from .update import _OPTS
        from .mesh import default_mesh
        if optimizer not in _OPTS:
            raise ValueError(f"PipelineTrainStep supports {sorted(_OPTS)}")
        self.mesh = mesh if mesh is not None else default_mesh()
        self.pp_axis = pp_axis
        opts = dict(optimizer_params or {})
        self.lr = opts.pop('learning_rate', opts.pop('lr', 0.01))
        self._opt_kwargs = opts
        self._opt_init, self._opt_update = _OPTS[optimizer]
        self._loss = pipeline_composite_loss(embed_fn, stage_fn, head_fn,
                                             loss_fn, self.mesh, pp_axis)

        pp_spec = P(pp_axis)
        self._specs = {
            'embed': jax.tree_util.tree_map(lambda _: P(), params['embed']),
            'stages': jax.tree_util.tree_map(lambda _: pp_spec,
                                             params['stages']),
            'head': jax.tree_util.tree_map(lambda _: P(), params['head']),
        }
        shardings = jax.tree_util.tree_map(
            lambda s: NamedSharding(self.mesh, s), self._specs,
            is_leaf=lambda x: isinstance(x, P))
        # copy=True: the step donates these buffers, and callers keep
        # using the source params (often live Gluon model weights)
        self._params = jax.tree_util.tree_map(
            lambda p, s: jax.device_put(jnp.array(p, copy=True), s),
            params, shardings)
        self._opt_state = jax.tree_util.tree_map(self._opt_init,
                                                 self._params)

        opt_kwargs = dict(self._opt_kwargs)
        lr = self.lr

        def step(ps, opt_state, x_mb, y_mb):
            loss, grads = jax.value_and_grad(self._loss)(ps, x_mb, y_mb)
            new_p = {}
            new_s = {}
            for group in ps:
                flat_p, treedef = jax.tree_util.tree_flatten(ps[group])
                flat_g = jax.tree_util.tree_leaves(grads[group])
                flat_s = treedef.flatten_up_to(opt_state[group])
                ups = [self._opt_update(p, g, s, lr, **opt_kwargs)
                       for p, g, s in zip(flat_p, flat_g, flat_s)]
                new_p[group] = jax.tree_util.tree_unflatten(
                    treedef, [u[0] for u in ups])
                new_s[group] = jax.tree_util.tree_unflatten(
                    treedef, [u[1] for u in ups])
            return loss, new_p, new_s

        self._compiled = jax.jit(step, donate_argnums=(0, 1))

    @property
    def params(self):
        return self._params

    def __call__(self, x_mb, y_mb):
        to_j = lambda a: a._data if hasattr(a, '_data') else jnp.asarray(a)
        x_mb = jax.tree_util.tree_map(to_j, x_mb)
        y_mb = jax.tree_util.tree_map(to_j, y_mb)
        loss, self._params, self._opt_state = self._compiled(
            self._params, self._opt_state, x_mb, y_mb)
        return loss

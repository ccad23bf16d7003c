"""Device-side names (ISSUE 26): the one pjit step is traced under phase
scopes and nested Gluon block paths, every Pallas kernel carries a name,
and none of it enters a cache key or changes a number. The names
themselves are listed in mxnet_tpu/scopes.py; the chip benchmark reads
them from a device trace (chipbench/scopes.py)."""
import contextlib
import re

import jax
import jax.numpy as jnp
import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd, scopes
from mxnet_tpu.gluon import nn
from mxnet_tpu.parallel import ShardedTrainStep, make_mesh
from mxnet_tpu.telemetry import compile as comp


# ---------------------------------------------------------------------------
# tiny steps
# ---------------------------------------------------------------------------

def _bert_step(zero=None, dp=1, guard=None, dropout=0.1):
    from mxnet_tpu.models import BertForPretraining
    from mxnet_tpu.models.bert import bert_pretrain_loss
    cfg = dict(vocab_size=128, hidden=32, layers=2, heads=2,
               intermediate=64, max_len=32, type_vocab=2, dropout=dropout)
    mx.random.seed(0)
    model = BertForPretraining(cfg)
    model.initialize(mx.init.Normal(0.02))
    step = ShardedTrainStep(model, bert_pretrain_loss, 'adamw',
                            {'learning_rate': 1e-3},
                            mesh=make_mesh((dp,), ('dp',)), zero=zero,
                            guard=guard)
    rng = onp.random.RandomState(0)
    batch, seq = 8, 16
    tokens = nd.array(rng.randint(0, 128, (batch, seq)).astype(onp.int32))
    types = nd.array(onp.zeros((batch, seq), onp.int32))
    labels = onp.full((batch, seq), -1, onp.int32)
    labels[:, :4] = rng.randint(0, 128, (batch, 4))
    return model, step, ([tokens, types], [
        nd.array(labels), nd.array(rng.randint(0, 2, batch)
                                   .astype(onp.int32))])


def _gpt_step():
    from mxnet_tpu.models import GPTModel, gpt_lm_loss
    mx.random.seed(0)
    model = GPTModel(vocab_size=128, hidden=32, layers=2, heads=4,
                     max_len=32, dropout=0.1)
    model.initialize(mx.init.Normal(0.02))
    step = ShardedTrainStep(model, gpt_lm_loss, 'adamw',
                            {'learning_rate': 1e-3},
                            mesh=make_mesh((1,), ('dp',)))
    toks = onp.random.RandomState(0).randint(0, 128, (4, 16)) \
        .astype(onp.int32)
    labels = onp.full_like(toks, -1)
    labels[:, :-1] = toks[:, 1:]
    return model, step, ([nd.array(toks)], [nd.array(labels)])


def _op_names(step, inputs):
    step(*inputs)
    return set(re.findall(r'op_name="([^"]*)"',
                          step.compiled_program().as_text()))


def _some(names, *parts):
    """An op_name that holds every part, in order."""
    pattern = re.compile('.*'.join(re.escape(p) for p in parts))
    return any(pattern.search(n) for n in names)


# ---------------------------------------------------------------------------
# the step's HLO speaks the program's names
# ---------------------------------------------------------------------------

def test_bert_step_hlo_carries_phases_and_block_paths():
    model, step, inputs = _bert_step()
    names = _op_names(step, inputs)
    top = model._local_name                     # bertforpretrainingN
    layer = 'bertmodel0/encoder/bertlayer1/'
    fwd = f'{scopes.FWD_BWD}/jvp({top})/{layer}'
    bwd = f'{scopes.FWD_BWD}/transpose(jvp({top}))/{layer}'
    # forward: jvp(<top block>) under the phase scope, nested block path
    # with the layer's index kept
    assert _some(names, f'jit(stable_step)/{fwd}',
                 'bertselfattention0/qkv/dot_general')
    assert _some(names, f'/jvp({top})/bertmodel0/encoder/bertlayer0/')
    # the stretches that are no block of their own
    for stretch in (f'bertselfattention0/{scopes.ATTN_CORE}/'
                    f'{scopes.ATTN_LAYOUT}/transpose',
                    f'{scopes.FFN1}/', f'{scopes.LN1}/', f'{scopes.LN2}/',
                    'ffn2/dot_general', 'bertselfattention0/proj/'):
        assert _some(names, fwd, stretch), stretch
    # backward: JAX's own transpose(jvp(...)) round the same path
    assert _some(names, bwd, 'ffn2/dot_general')
    assert _some(names, bwd, scopes.ATTN_LAYOUT)
    # head and loss
    assert _some(names, f'/jvp({top})/mlm_decoder/')
    assert _some(names, f'{scopes.FWD_BWD}/jvp({scopes.LOSS})/')
    assert _some(names, f'{scopes.FWD_BWD}/transpose(jvp({scopes.LOSS}))/')
    # after the gradients: the update, outside fwd_bwd (float32
    # parameters on one device leave the exchange nothing to do: the next
    # test sees it)
    assert _some(names, f'jit(stable_step)/{scopes.UPDATE}/')
    assert not _some(names, scopes.FWD_BWD, scopes.UPDATE)
    assert not _some(names, scopes.GUARD)       # no guard was asked for
    # nothing of the model runs outside the phase scope
    assert not [n for n in names
                if 'bertlayer' in n and scopes.FWD_BWD not in n]


def test_gpt_step_hlo_carries_phases_and_block_paths():
    model, step, inputs = _gpt_step()
    names = _op_names(step, inputs)
    top = model._local_name
    block = f'/jvp({top})/blocks/gptblock1/'
    assert _some(names, scopes.FWD_BWD + block, 'qkv/dot_general')
    assert _some(names, scopes.FWD_BWD + block, 'ffn1/')
    assert _some(names, scopes.FWD_BWD + block, 'layernorm1/')
    assert _some(names, scopes.FWD_BWD + block,
                 f'{scopes.ATTN_CORE}/{scopes.ATTN_LAYOUT}/')
    assert _some(names, f'/jvp({top})/blocks/gptblock0/')
    assert _some(names, f'/jvp({top})/{scopes.LM_HEAD}/dot_general')
    assert _some(names, f'/jvp({top})/ln_f/') \
        or _some(names, f'/jvp({top})/layernorm')
    assert _some(names, f'{scopes.FWD_BWD}/transpose(jvp({top}))/blocks/'
                 f'gptblock1/', 'ffn2/dot_general')
    assert _some(names, f'{scopes.FWD_BWD}/transpose(jvp({top}))/'
                 f'{scopes.LM_HEAD}/')
    assert _some(names, f'{scopes.FWD_BWD}/jvp({scopes.LOSS})/')
    assert _some(names, f'jit(stable_step)/{scopes.UPDATE}/')


def test_guard_and_zero3_gather_have_scopes_of_their_own():
    from mxnet_tpu.resilience import NonFiniteGuard
    _model, step, inputs = _bert_step(zero=3, dp=8, dropout=0.0,
                                      guard=NonFiniteGuard(policy='skip'))
    names = _op_names(step, inputs)
    assert _some(names, f'jit(stable_step)/{scopes.GUARD}/')
    assert _some(names, f'jit(stable_step)/{scopes.EXCHANGE}/')
    assert _some(names, f'jit(stable_step)/{scopes.UPDATE}/')
    # the per-layer gathers are inside value_and_grad: forward gather
    # under jvp(...), the gradient's reduce-scatter under transpose(...)
    assert _some(names, scopes.FWD_BWD, scopes.GATHER)


def test_block_local_names():
    net = nn.HybridSequential(prefix='net_')
    with net.name_scope():
        net.add(nn.Dense(4, in_units=3), nn.Dense(2, in_units=4),
                nn.Dense(2, in_units=2, prefix='head_'))
    assert net._local_name == 'net'
    assert [b._local_name for b in net] == ['dense0', 'dense1', 'head']
    assert net[2].name == 'net_head'
    assert nn.Dense(2, prefix='')._local_name == 'dense'


# ---------------------------------------------------------------------------
# every pallas_call carries its name
# ---------------------------------------------------------------------------

def _pallas_calls(jaxpr, found=None):
    found = [] if found is None else found
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == 'pallas_call':
            found.append(eqn.params['name'])
        for value in eqn.params.values():
            for v in (value if isinstance(value, (list, tuple))
                      else [value]):
                inner = getattr(v, 'jaxpr', v)
                inner = getattr(inner, 'jaxpr', inner)
                if hasattr(inner, 'eqns'):
                    _pallas_calls(inner, found)
    return found


def _flash_grad(x):
    from mxnet_tpu.ops.pallas_attention import flash_attention
    q = x.reshape(2, 2, 16, 8)
    return jnp.sum(flash_attention(q, q, q, interpret=True))


def _flash_grad_on_mesh(x):
    from mxnet_tpu.ops import attention
    with attention.mesh_placement(make_mesh((4,), ('dp',)), ('dp',), ()):
        return jnp.sum(attention.multi_head_attention(
            x, x, x, None, num_heads=4, use_pallas=True))


def _ffn(x):
    from mxnet_tpu.ops.pallas_ffn import fused_dense_gelu
    return jnp.sum(fused_dense_gelu(x, jnp.ones((16, 32)), jnp.ones(16),
                                    8, 8, True))


def _add_ln(x):
    from mxnet_tpu.ops.pallas_layernorm import fused_add_layer_norm
    return jnp.sum(fused_add_layer_norm(x, x, jnp.ones(32), jnp.ones(32),
                                        1e-5, 8, True))


@pytest.mark.parametrize('fn, shape, expected', [
    (_flash_grad, (2, 2 * 16 * 8),
     [scopes.FLASH_FWD, scopes.FLASH_BWD_DQ, scopes.FLASH_BWD_DKV]),
    (_flash_grad_on_mesh, (4, 16, 32),
     [scopes.FLASH_FWD, scopes.FLASH_BWD_DQ, scopes.FLASH_BWD_DKV]),
    (_ffn, (8, 32), [scopes.FFN_GELU]),
    (_add_ln, (8, 32), [scopes.ADD_LAYERNORM]),
], ids=['flash', 'flash_through_mesh_placement', 'ffn_gelu',
        'add_layernorm'])
def test_every_pallas_call_carries_its_name(fn, shape, expected):
    """Traced in interpret mode; the flash kernels also through
    mesh_placement's shard_map on a CPU mesh of four."""
    jaxpr = jax.make_jaxpr(jax.grad(fn))(jnp.ones(shape, jnp.float32))
    assert sorted(set(_pallas_calls(jaxpr.jaxpr))) == sorted(expected)


@pytest.mark.parametrize('use_pallas', [False, True], ids=['xla', 'pallas'])
def test_attn_layout_is_the_routes_that_compute_head_major(use_pallas):
    """The XLA route pays (N,T,H*D) <-> (N,H,T,D) under ``attn_layout``,
    forward and backward; the Pallas route has no such scope and no
    transpose: the kernels address the arrays as they are."""
    from mxnet_tpu.ops import attention
    from test_attention_layout import _walk as outside_kernels

    def loss(qkv):
        return jnp.sum(attention.self_attention(qkv, num_heads=4,
                                                use_pallas=use_pallas))
    jaxpr = jax.make_jaxpr(jax.grad(loss))(jnp.ones((2, 16, 96), jnp.float32))
    layout = [e.primitive.name for e in outside_kernels(jaxpr.jaxpr)
              if scopes.ATTN_LAYOUT in str(e.source_info.name_stack)]
    transposes = [e for e in outside_kernels(jaxpr.jaxpr)
                  if e.primitive.name == 'transpose'
                  and e.invars[0].aval.size >= 2 * 16 * 32]
    if use_pallas:
        assert not layout and not transposes
    else:
        # q, k, v and the result, and their cotangents
        assert layout.count('transpose') == 8 and len(transposes) >= 8


# ---------------------------------------------------------------------------
# the scope enters no cache key
# ---------------------------------------------------------------------------

class _Compiles:
    """Backend compile requests jax itself reports."""

    def __init__(self):
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, _duration, **_kw):
        if event == '/jax/core/compile/backend_compile_duration':
            self.n += 1


@pytest.fixture()
def compiles():
    return _Compiles()


@pytest.fixture()
def ledger():
    comp.clear(ledger='')
    comp.enable()
    yield comp
    comp.disable()
    comp.clear(ledger='')


def _dense(prefix):
    block = nn.Dense(5, in_units=7, activation='relu', prefix=prefix)
    block.initialize(mx.init.Xavier())
    return block


def test_eager_blocks_under_other_names_compile_nothing_new(compiles):
    x = nd.array(onp.ones((3, 7), onp.float32))
    first = _dense('alpha_')
    first(x).asnumpy()
    before = compiles.n
    other = _dense('beta_')
    assert other._local_name == 'beta' and first._local_name == 'alpha'
    other(x).asnumpy()
    with jax.named_scope('some_parent'):
        first(x).asnumpy()
    # the parameters differ, the programs do not: nothing compiled
    assert compiles.n == before


def test_hybridized_block_is_keyed_by_shapes_not_by_scope(compiles, ledger,
                                                           tmp_path):
    x = nd.array(onp.ones((3, 7), onp.float32))
    comp.clear(ledger='', cache_dir=str(tmp_path / 'xla_cache'))
    try:
        first = _dense('alpha_')
        first.hybridize()
        first(x).asnumpy()
        site = f'cachedop:{first.name}'
        assert len([e for e in ledger.ledger() if e['site'] == site]) == 1
        before, stats = compiles.n, comp.persistent_cache_stats()
        # the same block under another parent's scope: the CachedOp's key
        # holds shapes, dtypes and mode, and no name
        with jax.named_scope('some_parent'):
            first(x).asnumpy()
        assert len(ledger.ledger()) == 1 and compiles.n == before
        # a block of the same class under another name builds a CachedOp
        # of its own, as it always did; its program differs from the
        # first only in the metadata the scope wrote, which is no part of
        # the persistent cache's key: a hit, no miss
        other = _dense('beta_')
        other.hybridize()
        other(x).asnumpy()
        after = comp.persistent_cache_stats()
        assert after['misses'] == stats['misses']
        assert after['hits'] > stats['hits']
    finally:
        comp.clear(ledger='', cache_dir='')


def test_step_build_signature_holds_no_scope(ledger):
    _model, step, inputs = _bert_step()
    step(*inputs)
    entry, = [e for e in ledger.ledger() if e['site'] == 'step:train_step']
    assert entry['signature']
    assert 'mxtpu.' not in repr(entry['signature'])
    assert 'bertlayer' not in repr(entry['signature'])


# ---------------------------------------------------------------------------
# and changes no number
# ---------------------------------------------------------------------------

def test_loss_is_bit_equal_without_the_scopes(monkeypatch):
    def losses():
        _model, step, inputs = _bert_step()
        return [onp.float32(step(*inputs).asscalar()).tobytes()
                for _ in range(3)]

    scoped = losses()
    opened = []

    def no_scope(name):
        opened.append(name)
        return contextlib.nullcontext()

    monkeypatch.setattr(jax, 'named_scope', no_scope)
    bare = losses()
    assert scoped == bare
    # the patch did bite: the step asked for its scopes and got none
    assert {scopes.FWD_BWD, scopes.LOSS, scopes.EXCHANGE,
            scopes.UPDATE} <= set(opened)
    assert any(n.startswith('bertlayer') for n in opened)


def test_compiled_program_reads_its_own_names_through_a_warm_cache(tmp_path):
    """The persistent cache keys a program without its metadata: a model
    under another prefix (or an older source) with the same computation
    is a hit, and the executable that comes back carries the first
    program's op_names. compiled_program() is what a reader of names is
    handed, so it compiles with the metadata in the key."""
    def text_of(prefix):
        mx.random.seed(0)
        net = nn.Dense(4, in_units=6, prefix=prefix)
        net.initialize(mx.init.Xavier())
        step = ShardedTrainStep(
            net, mx.gluon.loss.L2Loss(), 'sgd', {'learning_rate': 0.1},
            mesh=make_mesh((1,), ('dp',)))
        step([nd.array(onp.ones((2, 6), onp.float32))],
             [nd.array(onp.ones((2, 4), onp.float32))])
        return step.compiled_program().as_text()

    comp.clear(ledger='', cache_dir=str(tmp_path / 'xla_cache'))
    try:
        first = text_of('alpha_')
        assert f'{scopes.FWD_BWD}/jvp(alpha)/' in first
        hits = comp.persistent_cache_stats()['hits']
        second = text_of('beta_')
        # the step itself was a hit on alpha's executable ...
        assert comp.persistent_cache_stats()['hits'] > hits
        # ... and the text still speaks of beta
        assert f'{scopes.FWD_BWD}/jvp(beta)/' in second
        assert 'jvp(alpha)' not in second
        assert jax.config.jax_compilation_cache_include_metadata_in_key \
            is False
    finally:
        comp.clear(ledger='', cache_dir='')


# ---------------------------------------------------------------------------
# the step runs the executable whose text it hands out (ISSUE 39)
# ---------------------------------------------------------------------------

class _BackendCompiles:
    """Backend compile requests jax reports, from the cache or not, since
    this object was made. jax keeps a listener for the life of the
    process, so the tests share one."""

    _seen = None

    def __init__(self):
        cls = type(self)
        if cls._seen is None:
            cls._seen = [0]
            jax.monitoring.register_event_duration_secs_listener(cls._on)
        self._start = cls._seen[0]

    @classmethod
    def _on(cls, event, _duration, **_kw):
        cls._seen[0] += event == '/jax/core/compile/backend_compile_duration'

    @property
    def n(self):
        return self._seen[0] - self._start


def _zero1_run(adopt, steps=3):
    """One step, then ``steps`` more, on a dp=4 mesh with ZeRO-1 and the
    guard; with ``adopt`` the step is asked for its program in between.
    (losses, parameters by name less the prefix, step, what the run saw)"""
    from mxnet_tpu.resilience import NonFiniteGuard
    model, step, batch = _bert_step(
        zero=1, dp=4, guard=NonFiniteGuard(policy='skip'))
    losses = [float(step(*batch).asscalar())]
    seen = {}
    if adopt:
        seen['donated'] = [p.data()._data
                           for p in model.collect_params().values()][:4]
        seen['exe'] = step.compiled_program()
    compiles = _BackendCompiles()
    losses += [float(step(*batch).asscalar()) for _ in range(steps)]
    seen['compiles'] = compiles.n
    cut = len(model.prefix)
    params = {n[cut:]: onp.asarray(p.data()._data)
              for n, p in model.collect_params().items()}
    return losses, params, step, seen


def test_the_step_runs_the_executable_it_hands_out():
    losses, params, step, seen = _zero1_run(adopt=True)
    exe = seen['exe']
    # one object, kept: a second asking compiles nothing and hands it back
    assert step.compiled_program() is exe
    assert step._executable is exe
    # the steps after the adoption compiled nothing: they ran `exe`
    assert seen['compiles'] == 0
    # ... which donates what the jitted step donates
    assert all(a.is_deleted() for a in seen['donated'])
    assert exe.memory_analysis().alias_size_in_bytes > 0
    # ... and takes the arrays where the layout placed them: the mesh's
    # shardings, ZeRO-1's state shards among them, and not one device's
    flat = jax.tree_util.tree_leaves(exe.input_shardings[0])
    assert all(len(s.device_set) == 4 for s in flat)
    twin_losses, twin_params, twin, twin_seen = _zero1_run(adopt=False)
    assert twin._executable is None and twin_seen['compiles'] == 0
    assert losses == twin_losses
    assert params.keys() == twin_params.keys()
    for name, value in params.items():
        assert onp.array_equal(value, twin_params[name]), name
    assert step._guard.bad_steps == twin._guard.bad_steps == 0


def test_a_step_never_asked_for_its_program_keeps_to_the_jitted_one():
    _model, step, batch = _bert_step()
    step(*batch)
    step(*batch)
    assert step._executable is None
    # cost_analysis() and memory_analysis() read the program too: both
    # adopt it, once
    cost = step.cost_analysis()
    assert cost is None or cost['flops'] > 0
    exe = step._executable
    assert exe is not None
    step.memory_analysis()
    assert step._executable is exe and step.compiled_program() is exe


def test_another_batch_shape_falls_back_to_the_jitted_step():
    model, step, (inputs, labels) = _bert_step()
    step(inputs, labels)
    exe = step.compiled_program()
    half = ([nd.array(x.asnumpy()[:4]) for x in inputs],
            [nd.array(x.asnumpy()[:4]) for x in labels])
    compiles = _BackendCompiles()
    assert onp.isfinite(float(step(*half).asscalar()))
    assert compiles.n > 0               # jit built the other shape
    assert step._executable is exe      # and the stored one stays ...
    before = compiles.n
    assert onp.isfinite(float(step(inputs, labels).asscalar()))
    assert compiles.n == before         # ... for the batch it was made for


def test_reset_mesh_drops_the_executable():
    _model, step, batch = _bert_step(zero=1, dp=4)
    first = float(step(*batch).asscalar())
    exe = step.compiled_program()
    step.reset_mesh(make_mesh((2,), ('dp',)))
    assert step._executable is None and step._compiled is None
    again = float(step(*batch).asscalar())
    assert onp.isfinite(again) and again < first
    assert step._executable is None
    assert step.compiled_program() is not exe


def test_the_executable_returns_guard_sparse_and_fault_outputs_in_order(
        monkeypatch):
    """The tail of the step's outputs — the loss, the guard's flag, the
    RowSparse statistics — and the fault scale among its inputs are read
    by position: the adopted executable hands them over as the jitted
    step does."""
    from mxnet_tpu.resilience import NonFiniteGuard, faults
    monkeypatch.setenv('MXTPU_SPARSE', '1')

    def run(adopt):
        mx.random.seed(11)
        net = nn.HybridSequential(prefix='sp_')
        with net.name_scope():
            net.add(nn.Embedding(200, 8, sparse_grad=True))
            net.add(nn.Dense(4, flatten=False))
        net.initialize()
        guard = NonFiniteGuard(policy='skip', max_consecutive_bad=10)
        step = ShardedTrainStep(
            net, lambda out, label: (out - label) ** 2, 'adam',
            {'learning_rate': 0.01}, mesh=make_mesh((2,), ('dp',)),
            guard=guard)
        rng = onp.random.RandomState(0)
        ids = nd.array(rng.randint(0, 40, (16, 5)).astype(onp.float32))
        lab = nd.array(rng.randn(16, 5, 4).astype(onp.float32))
        losses = [float(step(ids, lab).asscalar())]
        if adopt:
            step.compiled_program()
        faults.arm('step.dispatch', 'nan', window=(2, 3))
        try:
            losses += [float(step(ids, lab).asscalar()) for _ in range(4)]
        finally:
            faults.disarm()
        assert step._sparse_names and (step._executable is not None) is adopt
        live = {n: int(v) for n, v in step._sparse_prev_stats.items()}
        return losses, guard.bad_steps, live, net[0].weight.data().asnumpy()

    losses, bad, live, table = run(adopt=True)
    twin_losses, twin_bad, twin_live, twin_table = run(adopt=False)
    # dispatches 2 and 3 (counted from 0) were poisoned, and only they
    assert list(onp.isnan(losses)) == [False, False, True, True, False]
    assert bad == twin_bad == 2
    assert live == twin_live and all(0 < v <= 40 for v in live.values())
    onp.testing.assert_array_equal(losses, twin_losses)
    assert onp.array_equal(table, twin_table)

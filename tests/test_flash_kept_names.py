"""The names on what the flash forward hands its backward
(``ops/pallas_attention.py:_flash_fwd``, ``scopes.FLASH_KEPT``: ISSUE 38)
are read by a looped decoder's checkpoint regions and by nothing else.
Every other caller traces the rule too, and for it the two ``name``
equations are identities that lower to nothing: the tiny BERT, GPT-2 and
SmallThinker steps on the Pallas route lower to the text read on PR 37's
tree, before the rule named anything, and the step's own ``jax.checkpoint``
policies (``MXTPU_REMAT``, ZeRO-3's gather-drop) give the loss and the
weights of the program without the names, bit for bit. The kernels run
through the interpreter: the backend is the CPU.
"""
import hashlib
import os
import re
import sys

import jax
import numpy as onp
import pytest

from mxnet_tpu.ops import attention, pallas_attention
from mxnet_tpu.ops import moe    # noqa: F401  (bound to the backend's own
#                                   pallas_available before any test patches it)

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from chipbench import manifest, program, tokens    # noqa: E402

T = 64


@pytest.fixture
def flash_route(monkeypatch):
    """The attention of every layer on the Pallas route, as on a TPU."""
    monkeypatch.setattr(pallas_attention, 'pallas_available', lambda: True)
    monkeypatch.delenv('MXTPU_REMAT', raising=False)


def _unnamed(monkeypatch):
    """The forward rule as it was until ISSUE 38."""
    monkeypatch.setattr(pallas_attention, 'checkpoint_name',
                        lambda x, name: x)


def _tiny_step(name, dp=1, zero='default', **changed):
    """(step, one batch) of a cell at its rehearsal size, built as the
    benchmark builds it, on ``dp`` CPU devices (``labelled_positions``:
    BERT's prediction slots, fewer than T)."""
    cell = manifest.resolve(name)
    config = dict(cell.family.tiny(cell.config), **changed)
    traffic = dict(cell.traffic, per_chip_batch=2, seq_len=T,
                   labelled_positions=10, mesh={'dp': dp}, zero=zero)
    program.seed(5)
    model, loss_fn = cell.family.build(config)
    step = program.make_step(model, loss_fn, config, traffic,
                             jax.devices()[:dp])
    batch = tokens.make_ring(cell.family, config, traffic, 5, 2 * dp)[0]
    return step, batch


# (cell, what the tiny preset changes, (lines, sha256) of the canonical
# lowered step read on 988a3f5). SmallThinker's grouped heads are whole
# lane blocks on the Pallas route: the preset's heads of 16 would take
# the XLA route. Its expert layer stays on its XLA route (ops/moe.py asks
# the backend itself)
STEPS = {
    'bert': ('bert_base.t512', {}, (
        13233,
        'da7611069c257ac68f85ee2cf19b4bcc17122cf0e550acd70224d616c0d0a996')),
    'gpt2': ('gpt2_small.t1024', {}, (
        13414,
        '50ca8ae4cbca1db50457564a004f7edfda600ef585373f22707c8dc720118c94')),
    # the expert layer's lines moved when its grouped matmuls came to
    # stop at the live tiles (the combine's miss a select, the live tile
    # count); every other line is as read on 988a3f5
    'smallthinker': ('smallthinker_21b.t8192', {'head_dim': 128}, (
        23746,
        'a20cea75458417cac8a6d5e7426d28fa6217e2e266fa8a2dd3b079e4602cab9b')),
}


def _canonical(text):
    """Lowered text with its private functions numbered in the order they
    first appear: jax numbers them (``@_where_281``) from a counter that
    runs over the whole lowering, and the rule's two ``name`` equations,
    which emit nothing, move it on by one."""
    seen = {}
    return re.sub(
        r'@([A-Za-z_][\w.]*?)_(\d+)\b',
        lambda m: seen.setdefault(m.group(0), f'@{m.group(1)}#{len(seen)}'),
        text)


@pytest.mark.parametrize('model', sorted(STEPS))
def test_a_step_with_no_such_region_lowers_as_on_pr_37(flash_route,
                                                       monkeypatch, model):
    """The whole step program as XLA is handed it, value, gradient and
    update: the line count and hash read on 988a3f5 (private functions
    renumbered, :func:`_canonical`), and the same text with the names
    taken out again."""
    name, changed, (lines, digest) = STEPS[model]
    before = dict(attention.route_counts)
    step, (inputs, labels) = _tiny_step(name, **changed)
    text = _canonical(step.lower(inputs, labels).as_text())
    assert attention.route_counts['pallas'] > before['pallas']
    assert attention.route_counts['xla'] == before['xla']
    assert (len(text.splitlines()),
            hashlib.sha256(text.encode()).hexdigest()) == (lines, digest)
    _unnamed(monkeypatch)
    step, (inputs, labels) = _tiny_step(name, **changed)
    assert _canonical(step.lower(inputs, labels).as_text()) == text


def _two_steps(step, batch):
    """(the two losses, every parameter after them) as float32 bits."""
    inputs, labels = batch
    losses = [onp.asarray(program.payload(step(inputs, labels)),
                          onp.float32) for _ in range(2)]
    cut = len(step.block.prefix)
    return losses, {n[cut:]: onp.asarray(program.payload(p.data()),
                                         onp.float32)
                    for n, p in step.block.collect_params().items()}


@pytest.mark.parametrize('policy', ['none', 'layer', 'aggressive', 'zero3'])
def test_the_steps_own_checkpoint_policies_do_not_see_the_names(
        flash_route, monkeypatch, policy):
    """``dots_with_no_batch_dims_saveable`` and ``nothing_saveable``
    (parallel/step.py) ignore names, and ``save_any_names_but_these(
    'zero3_gather')`` (parallel/exchange.py) keeps every other value,
    named or not: two steps of the tiny causal model give the losses and
    the weights of the program that names nothing."""
    if policy == 'zero3':
        build = dict(dp=2, zero=3)
    else:
        monkeypatch.setenv('MXTPU_REMAT', policy)
        build = {}
    before = dict(attention.route_counts)
    step, batch = _tiny_step('gpt2_small.t1024', **build)
    losses, params = _two_steps(step, batch)
    assert step.zero_stage == (3 if policy == 'zero3' else 0)
    assert attention.route_counts['pallas'] > before['pallas']
    assert attention.route_counts['xla'] == before['xla']
    assert losses[1] < losses[0]
    _unnamed(monkeypatch)
    plain_losses, plain = _two_steps(*_tiny_step('gpt2_small.t1024', **build))
    onp.testing.assert_array_equal(losses, plain_losses)
    assert set(params) == set(plain) and len(params) > 20
    for n in params:
        onp.testing.assert_array_equal(params[n], plain[n], err_msg=n)

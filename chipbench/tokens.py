"""The one generator of training traffic: token streams and the ring of
host batches, from a seed and a traffic file's parameters.

Token ids follow a Zipf distribution over the vocabulary, so embedding
rows are hit as unevenly as text hits them and there is a unigram signal
for the loss to pick up. A family's ``make_batch`` shapes the stream into
its model's inputs (padding, masked positions, shifted labels).
"""
import numpy as onp


class Zipf:
    """Draws ids with P(rank r) proportional to r**-exponent. Ranks map to
    ids through a seeded permutation, so the frequent tokens are scattered
    over the embedding table as in a real vocabulary; ``reserved`` ids
    (mask, padding) are never drawn."""

    def __init__(self, rng, vocab_size, exponent, reserved=()):
        ids = onp.setdiff1d(onp.arange(vocab_size), onp.asarray(
            list(reserved), dtype=onp.int64))
        self._ids = rng.permutation(ids).astype(onp.int32)
        weights = onp.arange(1, len(ids) + 1, dtype=onp.float64) ** -exponent
        self._cdf = onp.cumsum(weights / weights.sum())

    def draw(self, rng, shape):
        ranks = onp.searchsorted(self._cdf, rng.random(shape), side='left')
        return self._ids[onp.minimum(ranks, len(self._ids) - 1)]


def source(family, config, traffic, seed, stream):
    """(rng, zipf) of one seeded stream of a run: the ring draws from one,
    the reference check from another, so neither moves the other."""
    rng = onp.random.default_rng([int(seed), stream])
    return rng, Zipf(rng, config['vocab_size'], traffic['zipf_exponent'],
                     family.reserved_ids(config))


def make_ring(family, config, traffic, seed, global_batch):
    """``ring_batches`` distinct host batches, each ``(inputs, labels)`` of
    int32 numpy arrays; the loop feeds them round robin, one per step."""
    rng, zipf = source(family, config, traffic, seed, 0x71F6)
    return [family.make_batch(config, traffic, rng, zipf, global_batch)
            for _ in range(traffic['ring_batches'])]

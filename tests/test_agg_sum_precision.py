"""A binned float `sum` stays within 1e-6 of the float64 sum over the
10^4-10^5 addends of a bucket of a large segment (ISSUE 28): the
scatter-add goes through interleaved partial accumulators and a
pairwise tree (`search/aggs/engine.py` `_scatter_sum`). The XLA:CPU
scatter adds a bin's addends one after another, which is the order the
guarantee has to survive; a plain float32 scatter-add does not."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from opensearch_tpu.search.aggs import engine

LIMIT = 1e-6


def column(n, bins, seed):
    rng = np.random.default_rng(seed)
    lanes = rng.integers(0, bins, n).astype(np.int32)
    lanes[rng.random(n) < 0.05] = -1            # lanes of no bin drop
    v = np.minimum(np.exp(rng.normal(8.0, 1.5, n)), 2 ** 23 - 1)
    v = v.astype(np.int32).astype(np.float32)   # sizes: whole, exact
    ok = lanes >= 0
    want = np.bincount(lanes[ok], weights=v[ok].astype(np.float64),
                       minlength=bins)
    return lanes, v, want


def widest_gap(got, want):
    got = np.asarray(got, dtype=np.float64)
    return float(np.max(np.abs(got - want) / np.maximum(want, 1e-300)))


@pytest.mark.parametrize("seed", [1, 2147483659, 3000000019])
def test_a_binned_float_sum_holds_1e_6_of_float64(seed):
    n, bins = 1 << 21, 24           # ~83,000 addends a bin
    lanes, v, want = column(n, bins, seed)

    @jax.jit
    def sums(lanes, v):
        return engine._binned_sums(lanes, bins, [(v, jnp.float32)], False)
    (got,) = sums(lanes, v)
    assert got.shape == (bins,) and got.dtype == jnp.float32
    assert widest_gap(got, want) < LIMIT / 3

    # the control: one sequential float32 accumulation a bin misses it
    @jax.jit
    def plain(lanes, v):
        safe = jnp.where(lanes >= 0, lanes, bins)
        return jnp.zeros(bins, jnp.float32).at[safe].add(v, mode="drop")
    assert widest_gap(plain(lanes, v), want) > LIMIT


def test_integer_counts_stay_exact_and_many_bins_fall_back():
    n, bins = 1 << 16, 300
    lanes, v, _ = column(n, bins, 7)
    ok = lanes >= 0
    (counts, sums) = jax.jit(lambda l, x: engine._binned_sums(
        l, bins, [(l >= 0, jnp.int32), (x, jnp.float32)], True))(lanes, v)
    assert counts.dtype == jnp.int32
    assert np.array_equal(np.asarray(counts),
                          np.bincount(lanes[ok], minlength=bins))
    want = np.bincount(lanes[ok], weights=v[ok].astype(np.float64),
                       minlength=bins)
    assert widest_gap(sums, want) < LIMIT / 3
    assert engine._sum_ways(bins) == engine.AGG_SUM_WAYS
    assert engine._sum_ways(engine.AGG_SUM_MAX_ACCUMULATORS) == 1
    assert engine._sum_ways(engine.AGG_SUM_MAX_ACCUMULATORS // 4) == 4

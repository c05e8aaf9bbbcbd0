"""A binned float `sum` stays within 1e-6 of the float64 sum over the
10^4-10^5 addends of a bucket of a large segment (ISSUE 28), and over
the ~10^7 of the largest bucket of a 2^25-lane segment (ISSUE 35): the
scatter-add goes through interleaved partial accumulators, as many as
the lanes scattered call for, and a pairwise tree
(`search/aggs/engine.py` `_scatter_sum`, `_sum_ways`). The XLA:CPU
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
    assert engine._sum_ways(bins, n) == engine.AGG_SUM_WAYS
    # many bins: fewer ways a bin, grown again only where a bin's room
    # calls for it and the grown budget has the accumulators
    small = engine.AGG_SUM_LANES_A_PARTIAL
    assert engine._sum_ways(engine.AGG_SUM_MAX_ACCUMULATORS, small) == 1
    assert engine._sum_ways(engine.AGG_SUM_MAX_ACCUMULATORS // 4,
                            4 * small) == 4
    assert engine._sum_ways(engine.AGG_SUM_MAX_ACCUMULATORS, n) == 16


ROOM_MIN = engine.AGG_BIN_ROOM_MIN


@pytest.mark.parametrize("bins,room,batch,ways", [
    (4592, ROOM_MIN, 1, 64),        # a row of the http_logs cell: as before
    (4592, 1 << 23, 1, 2048),       # were one of its bins to hold a row
    (9998, 1 << 24, 1, 4096),       # the nyc_taxis cell's histogram > stats
    (9998, 1 << 24, 8, 512),        # eight such queries in one program
    (9998, 1 << 24, 256, 64),       # a batch never goes under the base
    (50, 1 << 25, 1, 8192), (24, 1 << 16, 1, 64), (1, 1 << 25, 1, 8192),
    (1 << 22, 1 << 25, 1, 16), (1 << 20, 1 << 25, 1, 64),
    (1 << 22, 1 << 12, 1, 1), (1 << 22, 1 << 25, 64, 1),
])
def test_the_ways_grow_with_a_bins_room_and_fit_the_accumulators(
        bins, room, batch, ways):
    got = engine._sum_ways(bins, room, batch)
    assert got == ways
    assert got <= engine.AGG_SUM_WAYS or batch * bins * got \
        <= engine.AGG_SUM_MAX_GROWN


def test_a_bin_of_ten_million_cent_sized_addends_holds_1e_6():
    """The nyc_taxis cell's own count in one bin (1.1 x 10^7 of 2^24
    lanes), amounts in dollars and cents (not exact in float32)."""
    n, bins = 1 << 24, 4
    rng = np.random.default_rng(2147483659)
    lanes = (rng.random(n, dtype=np.float32) * 1.5).astype(np.int32)
    cents = np.clip(np.exp(rng.normal(7.0, 0.35, n).astype(np.float32)),
                    300, 50000).astype(np.int32)
    v = (cents / 100.0).astype(np.float32)
    want = np.bincount(lanes, weights=cents.astype(np.float64),
                       minlength=bins) / 100.0
    assert np.bincount(lanes).max() > 9_000_000

    @jax.jit
    def sums(lanes, v):
        return engine._binned_sums(lanes, bins, [(v, jnp.float32)], False)
    (got,) = sums(lanes, v)
    assert engine._sum_ways(bins, n) == 4096
    assert widest_gap(got, want) < LIMIT / 3

    # what the limit refuses, by 10x and more: one accumulation a bin
    @jax.jit
    def plain(lanes, v):
        return jnp.zeros(bins, jnp.float32).at[lanes].add(v, mode="drop")
    assert widest_gap(plain(lanes, v), want) > 10 * LIMIT


def test_a_levels_room_is_its_fullest_bucket_as_a_power_of_two():
    """`_bin_room`: counted once a (segment, key), no smaller than what
    the base ways cover, and nothing for a level nothing is summed
    under; `plan_bin_room` reads it back off a plan's `static`."""
    from types import SimpleNamespace as NS
    ctx = NS(seg=NS())
    calls = []

    def pops(values):
        def count():
            calls.append(1)
            return np.asarray(values)
        return count
    parent, leaf = NS(children=[object()]), NS(children=[])
    assert engine._bin_room(ctx, leaf, ("f", "a"), pops([9])) is None
    assert engine._bin_room(ctx, parent, ("f", "a"), pops([3, 20_000, 7])) \
        == ROOM_MIN == 64 * engine.AGG_SUM_LANES_A_PARTIAL
    assert engine._bin_room(ctx, parent, ("f", "a"), pops([1 << 30])) \
        == ROOM_MIN and len(calls) == 1             # the memo
    assert engine._bin_room(ctx, parent, ("f", "b"),
                            pops([11_610_287, 5])) == 1 << 24
    assert engine._bin_room(ctx, parent, ("f", "c"), pops([])) == ROOM_MIN
    num = engine.AggPlan("h", "bucket_num",
                         static=("f", 50, True, engine.BINS_TABLE, 1 << 24))
    assert engine.plan_bin_room(num) == 1 << 24
    assert engine.plan_bin_room(engine.AggPlan(
        "r", "bucket_num", static=("f", 5, True, engine.BINS_TABLE))) is None
    assert engine.plan_bin_room(engine.AggPlan(
        "t", "bucket_ord", static=("f", 5, True, ROOM_MIN))) == ROOM_MIN
    assert engine.plan_bin_room(engine.AggPlan(
        "m", "metric_num", static=("f", ("sum",), True))) is None
    # what the sums under such a level get: the cell's 4,096, a row of
    # the four-chip cell its 64
    assert engine._sum_ways(9998, 1 << 24) == 4096
    assert engine._sum_ways(4592, ROOM_MIN) == 64

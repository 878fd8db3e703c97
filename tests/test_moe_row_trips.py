"""The routed experts' row bookkeeping (``ops/moe_ops.py``): a trip of the
routed form takes the sorted rows that LANDED on the experts held, rounded
up to ONE trip: a row tile of the grouped product at decode sizes, as many
tiles as the width allows in a prompt's chunk.  The grouped
kernel runs in interpret mode and is held to the dense form; the counters
that say which trips an executable took and how full they were are held
to the rule."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu import profiler
from paddle_tpu.gen import GenPredictor
from paddle_tpu.models import block_moe, hybrid_moe
from paddle_tpu.ops import moe_ops

# 288 rows x top-4 are 1152 sorted rows (a prompt's chunk: trips of 512 at
# this width, the outputs scatter-added); 48 rows 192 (a decode step's:
# trips of one tile, the outputs summed by a one-hot product)
T, STEP, K, ALL, D, F = 288, 48, 4, 16, 32, 16


def _assignments(landed, held, offset, T=T):
    """``[T, K]`` picks of which the first ``landed`` places, row by
    row, fall on the ``held`` experts from ``offset`` (spread evenly: a
    tile's edge splits an expert) and the others on experts held
    elsewhere; a token's picks are distinct."""
    place = np.arange(T * K).reshape(T, K)
    t, j = np.divmod(place, K)
    here = offset + (t + j) % min(held, K) + (t // K % max(held // K, 1)) * K
    absent = (offset + held + (t + j) % (ALL - held)) % ALL \
        if held < ALL else here
    return jnp.asarray(np.where(place < landed, here, absent), jnp.int32)


CASES = {
    # rows, landed assignments, experts held, first held, chunk_rows, dead rows
    "no_row_landed": (T, 0, 4, 4, None, 0),
    "a_handful_landed": (T, 5, 4, 4, None, 0),
    "exactly_one_row_tile": (T, 128, 4, 4, None, 0),
    "one_more_than_a_tile": (T, 129, 4, 4, None, 0),
    "exactly_one_trip": (T, 512, 4, 4, None, 0),
    "one_more_than_a_trip": (T, 513, 4, 4, None, 0),
    "more_than_a_trip_landed": (T, 600, 4, 4, None, 0),
    "every_row_landed_in_one_trip": (T, T * K, ALL, 0, 0, 0),
    "every_row_landed_by_the_rule": (T, T * K, ALL, 0, None, 0),
    "dead_rows_through_live": (T, 300, 4, 8, None, 50),
    "a_step_a_handful_landed": (STEP, 5, 4, 4, None, 0),
    "a_step_one_more_than_a_tile": (STEP, 129, 4, 4, None, 0),
    "a_step_every_row_landed_in_one_trip": (STEP, STEP * K, ALL, 0, 0, 0),
    "a_step_dead_rows_through_live": (STEP, 100, 4, 8, None, 9),
}


# ``moe_experts`` (the ``relu2`` body) takes no ``chunk_rows``
@pytest.mark.parametrize("case, body", [
    (case, body) for case in CASES for body in ("gated", "relu2")
    if body == "gated" or CASES[case][4] is None])
def test_the_trips_follow_the_rows_that_landed(case, body):
    """Whatever landed (nothing, a handful, a tile to the row, a row
    more, more than a trip, everything, with dead rows) the routed form
    gives the dense form's numbers and its ``Stats``, and carries the
    landed rows rounded up to one trip."""
    T, landed, held, offset, chunk_rows, dead = CASES[case]
    rng = np.random.RandomState(len(case))
    x = jnp.asarray(rng.randn(T, D), jnp.float32)
    idx = _assignments(landed, held, offset, T)
    w = jnp.asarray(rng.rand(T, K), jnp.float32)
    live = None if not dead else jnp.arange(T) >= dead
    up = [jnp.asarray(rng.randn(held, D, F) * 0.1, jnp.float32)
          for _ in range(2 if body == "gated" else 1)]
    down = jnp.asarray(rng.randn(held, F, D) * 0.1, jnp.float32)
    if body == "gated":
        def run(routed):
            return moe_ops.moe_experts_gated(
                x, idx, w, *up, down, offset, live, routed=routed,
                interpret=True, chunk_rows=chunk_rows)
    else:
        def run(routed):
            return moe_ops.moe_experts(x, idx, w, *up, down, offset, live,
                                       routed=routed, interpret=True)
    got, stats = run(True)
    want, dense_stats = run(False)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=4e-6)
    assert np.asarray(stats).tolist() == np.asarray(dense_stats).tolist()
    n = landed if not dead else int(
        np.sum((np.arange(T * K).reshape(T, K) < landed)
               & (np.arange(T) >= dead)[:, None]))
    assert int(stats[0]) == n
    chunk = moe_ops.trip_rows(T * K, D, chunk_rows)
    assert chunk == (-(-T * K // 128) * 128 if chunk_rows == 0 else
                     512 if T * K > 1024 else 128)
    carried = moe_ops.rows_carried(n, chunk)
    assert carried % chunk == 0 and n <= carried < n + chunk


def test_the_trip_sizes_are_whole_row_tiles_of_what_a_layer_sorts():
    """A decode step's few assignments are one trip of their own size; a
    tile's worth and more take 128-row tiles; a layer that sorts more
    than 1024 rows (a prompt's chunk) as many tiles a trip as keep its
    float32 rows under 4 MiB, at most 512 rows: 512 of 1024 wide, 256
    of 4096, one tile of 6144 or 7168; ``chunk_rows`` 0 is one trip of
    everything and any other value trips of that size."""
    trips = moe_ops.trip_rows
    assert trips(4 * 6, 1024) == 32
    assert trips(16 * 8, 6144) == trips(32 * 8, 7168) == 128
    assert trips(64 * 8, 4096) == trips(32 * 22, 1024) == 128
    assert trips(128 * 8, 1024) == 128
    assert trips(129 * 8, 1024) == trips(1024 * 22, 1024) == 512
    assert trips(512 * 8, 4096) == trips(256 * 8, 4096) == 256
    assert trips(1024 * 8, 6144) == trips(1024 * 8, 7168) == 128
    assert trips(256 * 8, 2048, 0) == 2048
    assert trips(132 * 4, 32, 0) == 640
    assert trips(300 * 4, 32, 128) == 128
    assert trips(20 * 4, 32, 128) == 80
    # the rows the trips move: what landed, to the trip
    assert [moe_ops.rows_carried(n, 128)
            for n in (0, 1, 15, 128, 129, 512, 600)] == \
        [0, 128, 128, 128, 256, 512, 640]
    assert [moe_ops.rows_carried(n, 2048) for n in (0, 1, 2048)] == \
        [0, 2048, 2048]


def test_the_row_chunk_counter_fires_once_a_compiled_signature():
    count = profiler.runtime_metrics.counter
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(T, D), jnp.float32)
    idx, w = _assignments(40, 4, 4), jnp.ones((T, K), jnp.float32)
    wg, wu = (jnp.asarray(rng.randn(4, D, F), jnp.float32) for _ in "gu")
    wd = jnp.asarray(rng.randn(4, F, D), jnp.float32)

    @jax.jit
    def step(x):
        return moe_ops.moe_experts_gated(x, idx, w, wg, wu, wd, 4,
                                         routed=True, interpret=True)[0]
    was = {r: count(f"gen.moe.row_chunk.{r}") for r in (512, 128)}
    routed = count("gen.moe.routed_lowerings")
    for _ in range(3):
        step(x).block_until_ready()
    assert count("gen.moe.routed_lowerings") == routed + 1
    assert {r: count(f"gen.moe.row_chunk.{r}") - n
            for r, n in was.items()} == {512: 1, 128: 0}


def _turns(predictor, turns=3):
    """A few blocking decode turns with every slot live; the sums of
    ``moe_assignments`` their spans would carry, a layer's row each."""
    S, L = predictor.num_slots, predictor.block_length
    landed = []
    for s in range(S):
        predictor.alloc_slot_pages(s, predictor.pages_needed(8, 4 * L))
    for turn in range(turns):
        pos = np.full(S, 8 + turn * L, np.int32)
        predictor.decode_step(np.arange(S) % 7 + 1, pos, pos + L)
        landed.append(np.asarray(predictor.last_decode_stats)[:, 0])
    return np.stack(landed)


@pytest.mark.parametrize("share", ["a_share_of_the_experts", "every_expert"])
def test_rows_landed_over_rows_carried_says_how_full_the_trips_are(
        tmp_path, share):
    """``gen.moe.rows_landed`` / ``gen.moe.rows_carried`` from the
    ``Stats`` a turn fetches anyway: a share-cut bundle's layers carry a
    tile for the few rows that land, one that holds every expert its
    whole sorted rows in one trip."""
    path = str(tmp_path / "bundle")
    if share == "every_expert":
        hp = block_moe.BlockMoEConfig()
        hp.dtype, hp.max_len = "float32", 64
        block_moe.export_block_model(path, hp, num_slots=4,
                                     prompt_buckets=[8, 16], page_len=8)
    else:
        hp = hybrid_moe.HybridConfig()
        hp.dtype, hp.max_len = "float32", 64
        hp.experts_held, hp.expert_offset = 4, 4
        hybrid_moe.export_hybrid_model(path, hp, num_slots=4,
                                       prompt_buckets=[8, 16], page_len=8)
    p = GenPredictor(path)
    count = profiler.runtime_metrics.counter
    was = count("gen.moe.rows_landed"), count("gen.moe.rows_carried")
    landed = _turns(p)
    assert len(p._moe_trips) == landed.shape[1] > 0
    if share == "every_expert":
        # a step's shape holds two blocks a slot; whatever is live lands,
        # and ONE trip takes the layer's sorted rows whole
        whole = 4 * 2 * p.block_length * int(hp.num_experts_per_tok)
        assert p._moe_trips == [whole] * landed.shape[1]
        assert (landed > 0).all() and (landed % p.block_length == 0).all()
    else:
        rows = 4 * int(hp.num_experts_per_tok)
        assert p._moe_trips == [-(-rows // 16) * 16] * landed.shape[1]
        assert (landed < rows).any()
    carried = sum(moe_ops.rows_carried(int(n), chunk)
                  for turn in landed for n, chunk in zip(turn, p._moe_trips))
    assert count("gen.moe.rows_landed") - was[0] == landed.sum()
    assert count("gen.moe.rows_carried") - was[1] == carried
    assert 0 < landed.sum() <= carried

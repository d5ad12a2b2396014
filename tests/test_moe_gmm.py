"""The Pallas grouped product ``moe_gmm`` (ISSUE 33) on the CPU in interpret
mode, against a per-group ``jnp.dot`` loop: what a step's ragged groups
can look like (an empty expert, a group of one row, a group across two row
tiles, one expert with most rows, dead rows behind the last group), both
operand dtypes, the gated form and the visit metadata."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.fluid.ops.pallas_kernels.moe_gmm import (group_visits,
                                                         moe_gmm)

TM = 16     # the tests' row tile: groups straddle it at toy row counts


def _loop(lhs, rhs, counts, gate=None):
    """Each group's rows times its expert's matrix, float32 accumulation;
    rows of no group are zeros."""
    out = np.zeros((lhs.shape[0], rhs.shape[2]), np.float32)
    start = 0
    for g, n in enumerate(counts):
        x = lhs[start:start + n]
        y = jnp.dot(x, rhs[g], preferred_element_type=jnp.float32)
        if gate is not None:
            y = jax.nn.silu(jnp.dot(
                x, gate[g], preferred_element_type=jnp.float32)) * y
        out[start:start + n] = np.asarray(y)
        start += n
    return out


def _operands(rows, experts, k, n, dtype, seed=0):
    rng = np.random.RandomState(seed)
    draw = lambda *s: jnp.asarray(rng.randn(*s) / np.sqrt(s[-2]), dtype)
    return (jnp.asarray(rng.randn(rows, k), dtype), draw(experts, k, n),
            draw(experts, k, n))


# (rows, experts, K, N, counts): every shape the issue names, each a case
CASES = {
    "an_empty_expert": (48, 4, 128, 256, [10, 0, 33, 5]),
    "a_group_of_one_row": (32, 4, 128, 128, [1, 16, 1, 14]),
    "a_group_across_two_row_tiles": (48, 3, 128, 128, [9, 14, 25]),
    "a_group_across_three_row_tiles": (64, 3, 256, 128, [7, 41, 16]),
    "one_expert_holds_most_rows": (96, 8, 128, 128,
                                   [2, 1, 0, 3, 1, 80, 0, 2]),
    "rows_behind_the_last_group": (80, 4, 128, 384, [5, 0, 12, 6]),
    "whole_row_tiles_behind_the_last_group": (128, 4, 128, 128,
                                              [3, 3, 3, 3]),
    "every_row_in_the_last_expert": (32, 5, 128, 128, [0, 0, 0, 0, 32]),
    "groups_end_on_tile_edges": (64, 4, 128, 128, [16, 32, 0, 16]),
    "rows_not_a_multiple_of_the_tile": (41, 3, 128, 128, [20, 1, 17]),
    "more_experts_than_row_tiles": (16, 12, 128, 128,
                                    [1, 0, 2, 0, 0, 3, 1, 1, 0, 4, 0, 2]),
}


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_equals_the_per_group_dot_loop(case, dtype):
    rows, experts, k, n, counts = CASES[case]
    lhs, rhs, _ = _operands(rows, experts, k, n, dtype)
    got = np.asarray(jax.jit(lambda l, r, c: moe_gmm(
        l, r, c, interpret=True, row_tile=TM))(
            lhs, rhs, jnp.asarray(counts, jnp.int32)))
    want = _loop(lhs, rhs, counts)
    live = sum(counts)
    assert got.shape == want.shape and got.dtype == np.float32
    # the same operands and a float32 accumulator: summation order only
    np.testing.assert_allclose(got[:live], want[:live],
                               atol=2e-5 if dtype == "float32" else 2e-4)
    # inside a visited row tile the rows of no group are zeros
    edge = min(-(-live // TM) * TM, rows)
    assert not got[live:edge].any()


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", ["an_empty_expert",
                                  "a_group_across_two_row_tiles",
                                  "one_expert_holds_most_rows"])
def test_gated_form_is_silu_gate_times_up_rounded_once(case, dtype):
    rows, experts, k, n, counts = CASES[case]
    lhs, up, gate = _operands(rows, experts, k, n, dtype, seed=1)
    got = jax.jit(lambda l, u, g, c: moe_gmm(
        l, u, c, gate=g, interpret=True, row_tile=TM))(
            lhs, up, gate, jnp.asarray(counts, jnp.int32))
    assert got.dtype == lhs.dtype          # written in the rows' dtype
    want = _loop(lhs, up, counts, gate).astype(got.dtype)
    live = sum(counts)
    np.testing.assert_allclose(
        np.asarray(got[:live], np.float32), np.asarray(want[:live],
                                                       np.float32),
        atol=2e-5 if dtype == "float32" else 0, rtol=0 if dtype == "float32"
        else 2 ** -7)                      # bfloat16: within one rounding


@pytest.mark.parametrize("garbage", ["nan", "inf", "huge"])
def test_dead_rows_reach_no_live_row(garbage):
    """Rows are independent: with NaN, infinity or 1e30 in the rows behind
    the last group the live rows are BITWISE what they are with zeros
    there, in the plain and in the gated form."""
    rows, experts, k, n, counts = CASES["rows_behind_the_last_group"]
    lhs, up, gate = _operands(rows, experts, k, n, "float32", seed=2)
    live = sum(counts)
    fill = {"nan": np.nan, "inf": np.inf, "huge": 1e30}[garbage]
    c = jnp.asarray(counts, jnp.int32)
    for g in (None, gate):
        fn = jax.jit(lambda l: moe_gmm(l, up, c, gate=g, interpret=True,
                                       row_tile=TM))
        clean = np.asarray(fn(lhs.at[live:].set(0.0)))
        dirty = np.asarray(fn(lhs.at[live:].set(fill)))
        assert np.array_equal(clean[:live], dirty[:live])
        assert np.isfinite(dirty[:live]).all()


def test_no_live_row_at_all_is_one_empty_visit():
    lhs, rhs, _ = _operands(32, 3, 128, 128, "float32")
    got = np.asarray(moe_gmm(lhs, rhs, jnp.zeros((3,), jnp.int32),
                             interpret=True, row_tile=TM))
    assert not got[:TM].any()              # the one visit wrote zeros


@pytest.mark.parametrize("case", sorted(CASES))
def test_visits_cover_every_live_row_once_and_no_empty_expert(case):
    rows, experts, _, _, counts = CASES[case]
    rows = -(-rows // TM) * TM
    offsets, groups, tiles, n = (np.asarray(a) for a in group_visits(
        jnp.asarray(counts, jnp.int32), rows, TM))
    assert groups.shape == tiles.shape == (rows // TM + experts - 1,)
    assert np.array_equal(offsets, np.concatenate([[0], np.cumsum(counts)]))
    visits = list(zip(groups[:n].tolist(), tiles[:n].tolist()))
    want = [(g, t) for g, c in enumerate(counts) if c
            for t in range(offsets[g] // TM, (offsets[g + 1] - 1) // TM + 1)]
    # by group then tile is by tile then group: a group across two tiles
    # is two consecutive visits (its weights are fetched once), a tile's
    # visits are consecutive (its output block is written back once)
    assert visits == want == sorted(want, key=lambda v: (v[1], v[0]))
    # what lies past n is in range: the pipeline may look one step ahead
    assert (groups < experts).all() and (tiles < rows // TM).all()
    assert (groups >= 0).all() and (tiles >= 0).all()


def test_widths_off_the_lane_tile_are_refused_by_name():
    lhs, rhs, _ = _operands(16, 2, 64, 128, "float32")
    with pytest.raises(ValueError, match="multiples of 128"):
        moe_gmm(lhs, rhs, jnp.asarray([8, 8], jnp.int32), interpret=True)

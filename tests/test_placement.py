"""Unit tests for the O(n) selection kernels (selectk) and the unified
Placement substrate — the pieces the fused epoch_step is built from.

selectk's contract is *bit-equivalence* with the sort-based primitives it
replaces (lax.top_k / stable argsort), including tie-breaks, so these tests
compare against those references directly on tie-heavy inputs."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest
from functools import partial

from repro.core import policy, selectk
from repro.core.placement import Placement, apply_plan, demote_idle, plan_promotion


# ------------------------------------------------------------------ selectk
@pytest.mark.parametrize("n,k,lo,hi", [
    (10_000, 500, 0, 5),        # heavy ties
    (10_000, 500, -3, 3),       # negatives
    (5_000, 5_000, 0, 1),       # k == n, near-constant
    (10_000, 1, -100, 100),
    (777, 77, 0, 1_000_000),    # wide range, odd length (cumsum fallback)
])
def test_select_top_k_matches_lax_top_k(n, k, lo, hi):
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.integers(lo, hi + 1, n).astype(np.int32))
    v_ref, i_ref = jax.lax.top_k(x, k)
    v, i = jax.jit(partial(selectk.select_top_k, k=k))(x)
    np.testing.assert_array_equal(np.asarray(v_ref), np.asarray(v))
    np.testing.assert_array_equal(np.asarray(i_ref), np.asarray(i))


def test_select_top_k_batched_and_mask():
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.integers(0, 4, (3, 2_000)).astype(np.int32))
    v, i, sel = jax.jit(partial(selectk.select_top_k, k=150,
                                return_mask=True))(x)
    for row in range(3):
        v_ref, i_ref = jax.lax.top_k(x[row], 150)
        np.testing.assert_array_equal(np.asarray(v_ref), np.asarray(v[row]))
        np.testing.assert_array_equal(np.asarray(i_ref), np.asarray(i[row]))
        mask_ref = np.zeros(2_000, bool)
        mask_ref[np.asarray(i_ref)] = True
        np.testing.assert_array_equal(mask_ref, np.asarray(sel[row]))


def test_select_top_k_float_keys_via_bitcast():
    """Non-negative float scores select identically through sortable_key —
    the order isomorphism the proactive/hinted lanes rely on."""
    rng = np.random.default_rng(2)
    xf = jnp.asarray(np.abs(rng.normal(size=4_096)).astype(np.float32)
                     * (rng.random(4_096) < 0.5))
    _, i_ref = jax.lax.top_k(xf, 400)
    _, i = jax.jit(partial(selectk.select_top_k, k=400))(
        selectk.sortable_key(xf))
    np.testing.assert_array_equal(np.asarray(i_ref), np.asarray(i))


def test_bottom_k_mask_matches_stable_argsort_prefix():
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.integers(0, 6, 3_000).astype(np.int32))
    for cnt in (0, 1, 700, 3_000):
        ref = np.zeros(3_000, bool)
        ref[np.argsort(np.asarray(x), kind="stable")[:cnt]] = True
        got = np.asarray(jax.jit(selectk.bottom_k_mask)(x, jnp.asarray(cnt)))
        np.testing.assert_array_equal(ref, got, err_msg=str(cnt))


def test_stable_rank_sparse_matches_double_argsort():
    rng = np.random.default_rng(4)
    for n, n_pos in ((2_048, 0), (2_048, 1), (2_048, 37), (1_000, 1_000)):
        x = np.zeros(n, np.int32)
        pos = rng.choice(n, n_pos, replace=False)
        x[pos] = rng.integers(1, 5, n_pos)       # duplicate positive values
        xj = jnp.asarray(x)
        ref = np.asarray(jnp.argsort(jnp.argsort(xj)))
        got = np.asarray(jax.jit(partial(
            selectk.stable_rank_sparse, max_positive=max(n_pos, 1)))(xj))
        np.testing.assert_array_equal(ref, got)


def _counts(n_sel, n, seed):
    """(..., n) inclusive prefix counts of masks with ``n_sel[row]`` true
    entries at random positions (one row per entry of ``n_sel``)."""
    rng = np.random.default_rng(seed)
    rows = np.zeros((len(n_sel), n), bool)
    for r, m in enumerate(n_sel):
        rows[r, rng.choice(n, m, replace=False)] = True
    return jnp.asarray(np.cumsum(rows, axis=-1, dtype=np.int32))


def _searchsorted_rows(csel, k):
    targets = jnp.arange(1, k + 1, dtype=csel.dtype)
    rows = csel.reshape((-1, csel.shape[-1]))
    return np.stack([np.asarray(jnp.searchsorted(r, targets, side="left"))
                     for r in rows]).reshape(csel.shape[:-1] + (k,))


COMPACT_CASES = {        # name: (selected per row, n, k, batched)
    "fewer_than_k": ((50,), 1_000, 200, False),    # fills with n
    "exactly_k": ((100,), 1_000, 100, False),
    "more_than_k": ((300,), 1_000, 60, False),
    "batched": ((100, 150, 400), 1_500, 150, True),
    "k_eq_n": ((150, 512), 512, 512, True),        # the free-slot shape
    "n_1_selected": ((1,), 1, 1, False),
    "n_1_empty": ((0,), 1, 1, False),
}


@pytest.mark.parametrize("impl", ["scatter", "search"])
@pytest.mark.parametrize("case", sorted(COMPACT_CASES))
def test_compact_paths_match_searchsorted(case, impl):
    n_sel, n, k, batched = COMPACT_CASES[case]
    csel = _counts(n_sel, n, seed=len(case))
    if not batched:
        csel = csel[0]
    fn = {"scatter": lambda c: selectk._compact_scatter(c, k),
          "search": lambda c: selectk._compact_search(c, k)}[impl]
    got = np.asarray(jax.jit(fn)(csel))
    assert got.dtype == np.int32
    np.testing.assert_array_equal(_searchsorted_rows(csel, k), got)


@pytest.mark.parametrize("n,k,impl", [
    (4_000, 400, "scatter"),
    (4_000, 10, "search"),
    (512, 512, "scatter"),
])
def test_compact_takes_the_path_its_shape_picks(n, k, impl):
    assert selectk.compact_impl(n, k) == impl
    csel = _counts((k // 2, k, min(2 * k, n)), n, seed=n + k)
    np.testing.assert_array_equal(
        _searchsorted_rows(csel, k),
        np.asarray(jax.jit(lambda c: selectk.compact(c, k))(csel)))


@pytest.mark.parametrize("n,k,impl", [
    (5_000_000, 486_587, "scatter"),    # DLRM select
    (486_587, 486_587, "scatter"),      # DLRM free slots
    (2_621_440, 262_144, "scatter"),    # mmap-bench select
    (5_000_000, 32_768, "search"),      # stable_rank_sparse's bound, DLRM
    (2_621_440, 16_384, "search"),      # ... and mmap-bench
    (5_000_000, 72_464, "scatter"),     # either side of n / (3 * 23)
    (5_000_000, 72_463, "search"),
    (1, 1, "scatter"),
])
def test_compact_rule_crossover(n, k, impl):
    assert selectk.compact_impl(n, k) == impl


def test_prefix_sum_matches_cumsum():
    rng = np.random.default_rng(5)
    for shape in ((1_024,), (3, 2_048), (5, 1_000)):   # incl. fallback path
        x = jnp.asarray((rng.random(shape) < 0.4))
        np.testing.assert_array_equal(
            np.asarray(jnp.cumsum(x.astype(jnp.int32), axis=-1)),
            np.asarray(jax.jit(selectk.prefix_sum)(x)))


# ---------------------------------------------------------------- placement
def _check_maps(p: Placement):
    s2b = np.asarray(p.slot_to_block)
    b2s = np.asarray(p.block_to_slot)
    for lane in range(s2b.shape[0]) if s2b.ndim == 2 else [slice(None)]:
        s, b = s2b[lane], b2s[lane]
        assert (s >= 0).sum() == (b >= 0).sum()
        for slot, blk in enumerate(s):
            if blk >= 0:
                assert b[blk] == slot
        for blk, slot in enumerate(b):
            if slot >= 0:
                assert s[slot] == blk


def test_apply_plan_fills_free_slots_in_priority_order():
    p = Placement.create(16, 4)
    est = jnp.zeros((16,), jnp.float32)
    want = jnp.asarray([7, 3, 9, -1, -1, -1], jnp.int32)
    p2, promoted, demoted = jax.jit(apply_plan)(p, want, est)
    assert int(promoted) == 3 and int(demoted) == 0
    np.testing.assert_array_equal(np.asarray(p2.slot_to_block), [7, 3, 9, -1])
    _check_maps(p2)


def test_apply_plan_evicts_coldest_never_wanted():
    """Full tier + a plan that keeps one resident: the eviction must take
    the coldest non-wanted residents, never the still-wanted one."""
    p = Placement.create(16, 3)
    est0 = jnp.zeros((16,), jnp.float32)
    p, _, _ = apply_plan(p, jnp.asarray([5, 6, 7], jnp.int32), est0)
    est = jnp.zeros((16,), jnp.float32).at[5].set(1.0).at[6].set(50.0).at[7].set(10.0)
    want = jnp.asarray([6, 0, 1], jnp.int32)     # 6 already fast, 0/1 new
    p2, promoted, demoted = jax.jit(apply_plan)(p, want, est)
    assert int(promoted) == 2 and int(demoted) == 2
    s2b = set(np.asarray(p2.slot_to_block).tolist())
    assert s2b == {6, 0, 1}                      # 5 and 7 evicted, 6 kept
    _check_maps(p2)


def test_apply_plan_lane_stacked_matches_per_lane():
    rng = np.random.default_rng(6)
    n, k, L = 64, 8, 4
    s2b = np.full((L, k), -1, np.int32)
    b2s = np.full((L, n), -1, np.int32)
    for lane in range(L):                        # random consistent placements
        blocks = rng.choice(n, rng.integers(0, k + 1), replace=False)
        for slot, blk in enumerate(blocks):
            s2b[lane, slot] = blk
            b2s[lane, blk] = slot
    stacked = Placement(slot_to_block=jnp.asarray(s2b),
                        block_to_slot=jnp.asarray(b2s))
    # unique ids with -1 padding interleaved (apply_plan's contract: plans
    # come from top_k, so ids never repeat)
    want_np = np.stack([rng.permutation(n)[:k] for _ in range(L)])
    want_np[rng.random((L, k)) < 0.3] = -1
    want = jnp.asarray(want_np.astype(np.int32))
    est = jnp.asarray(rng.integers(0, 10, (L, n)).astype(np.float32))
    out, promoted, demoted = jax.jit(apply_plan)(stacked, want, est)
    for lane in range(L):
        single = Placement(slot_to_block=jnp.asarray(s2b[lane]),
                           block_to_slot=jnp.asarray(b2s[lane]))
        o, pr, de = apply_plan(single, want[lane], est[lane])
        np.testing.assert_array_equal(np.asarray(o.slot_to_block),
                                      np.asarray(out.slot_to_block)[lane])
        np.testing.assert_array_equal(np.asarray(o.block_to_slot),
                                      np.asarray(out.block_to_slot)[lane])
        assert int(pr) == int(np.asarray(promoted)[lane])
        assert int(de) == int(np.asarray(demoted)[lane])
    _check_maps(out)


def test_demote_idle_frees_untouched_residents_only_when_enabled():
    p = Placement.create(8, 3)
    p, _, _ = apply_plan(p, jnp.asarray([1, 2, 4], jnp.int32),
                         jnp.zeros((8,), jnp.float32))
    est = jnp.zeros((8,), jnp.float32).at[2].set(3.0)
    p_on, n_on = jax.jit(demote_idle)(p, est, True)
    assert int(n_on) == 2                        # blocks 1 and 4 idle
    assert set(np.asarray(p_on.slot_to_block).tolist()) == {2, -1}
    p_off, n_off = jax.jit(demote_idle)(p, est, False)
    assert int(n_off) == 0
    np.testing.assert_array_equal(np.asarray(p_off.slot_to_block),
                                  np.asarray(p.slot_to_block))
    _check_maps(p_on)


def test_plan_promotion_host_helper_guards_wanted_blocks():
    """The host control-plane variant (TieredEmbedding's path) applies the
    same plan_eviction invariant: victims are coldest non-wanted residents,
    sized to exactly cover the shortfall."""
    p = Placement.create(16, 3)
    p, _, _ = apply_plan(p, jnp.asarray([5, 6, 7], jnp.int32),
                         jnp.zeros((16,), jnp.float32))
    est = np.zeros(16); est[[5, 6, 7]] = [1.0, 50.0, 10.0]
    want, victims = plan_promotion(
        p, jnp.asarray([6, 0, 1, -1], jnp.int32), est)
    assert want.tolist() == [6, 0, 1]
    v = np.asarray(victims)
    assert set(v[v >= 0].tolist()) == {5, 7}
    # nothing to evict when promotions fit
    _, none_victims = plan_promotion(p, jnp.asarray([6], jnp.int32), est)
    assert none_victims is None


def test_policy_hinted_gates_unhinted_untouched_blocks():
    """Satellite: zero-telemetry zero-hint blocks are never promoted just to
    fill k — they would churn migration traffic for no signal."""
    counts = jnp.asarray([0, 9, 0, 0, 3, 0], jnp.int32)
    hints = jnp.zeros((6,), jnp.float32).at[2].set(0.8)
    plan = policy.hinted(counts, hints, k=6, hint_weight=0.5)
    got = [int(x) for x in np.asarray(plan.promote) if x >= 0]
    assert set(got) == {1, 2, 4}                 # only telemetry or hint
    # all-cold, no hints -> empty plan
    empty = policy.hinted(jnp.zeros((6,), jnp.int32),
                          jnp.zeros((6,), jnp.float32), k=4)
    assert (np.asarray(empty.promote) == -1).all()

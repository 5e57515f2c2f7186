"""observe_scatter: fused telemetry scatter vs its oracle, and the fused
``observe_all`` epoch path with the kernel swapped in.

The kernel must reproduce the XLA scatter-adds bit for bit — including the
``mode="drop"`` semantics where a negative id wraps once (NumPy-style) and
only ids still outside ``[0, n_blocks)`` are dropped — because its two
histograms feed every collector update in the epoch scan."""
import dataclasses

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import telemetry as tel
from repro.faults.model import FaultModel
from repro.kernels.dispatch import PallasBackend
from repro.kernels.observe_scatter import observe_scatter, observe_scatter_ref

BACKEND = PallasBackend(interpret=True, scatter_tile_m=256)


def _bundles_equal(a, b):
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    return len(la) == len(lb) and all(
        np.array_equal(np.asarray(x), np.asarray(y))
        for x, y in zip(la, lb))


# ----------------------------------------------------------- kernel parity
@pytest.mark.parametrize("m,n_blocks,period,cursor", [
    (512, 100, 37, 0),
    (1000, 997, 7, 11),        # ragged final tile
    (37, 50, 1, 3),            # every position sampled
    (256, 64, 10007, 10006),   # cursor wraps mid-batch
])
def test_observe_scatter_matches_ref(m, n_blocks, period, cursor):
    rng = np.random.default_rng(0)
    # ids straddle the valid range on both sides: negatives wrap once,
    # >= n_blocks drops — exactly XLA's .at[ids].add(mode="drop")
    ids = jnp.asarray(
        rng.integers(-3, n_blocks + 3, size=(m,)).astype(np.int32))
    keep = jnp.asarray(rng.random(m) < 0.6)
    cur = jnp.asarray(cursor, jnp.int32)
    for km in (None, keep):
        h_ref, p_ref = observe_scatter_ref(ids, cur, n_blocks=n_blocks,
                                           period=period, keep=km)
        h_pal, p_pal = observe_scatter(ids, cur, n_blocks=n_blocks,
                                       period=period, keep=km,
                                       tile_m=BACKEND.scatter_tile_m,
                                       use_pallas=True, interpret=True)
        np.testing.assert_array_equal(np.asarray(h_ref), np.asarray(h_pal))
        np.testing.assert_array_equal(np.asarray(p_ref), np.asarray(p_pal))


def test_observe_scatter_ref_matches_telemetry_scatters():
    """The oracle IS the telemetry path: same histograms the per-collector
    .at[].add scatters produce."""
    rng = np.random.default_rng(1)
    n_blocks, m = 200, 777
    ids = jnp.asarray(rng.integers(0, n_blocks, m).astype(np.int32))
    cur = jnp.asarray(5, jnp.int32)
    period = 13
    h, p = observe_scatter_ref(ids, cur, n_blocks=n_blocks, period=period)
    np.testing.assert_array_equal(
        np.asarray(h),
        np.bincount(np.asarray(ids), minlength=n_blocks))
    hit = (np.asarray(cur) + np.arange(m)) % period == 0
    np.testing.assert_array_equal(
        np.asarray(p),
        np.bincount(np.asarray(ids)[hit], minlength=n_blocks))


@pytest.mark.parametrize("interpret", [False, True])
def test_observe_scatter_explicit_at_any_size(interpret):
    """No quiet size switch: the kernel runs (interpreted) past the old 1M
    bound, and the compiled kernel, which Mosaic refuses, is an error."""
    n_blocks = (1 << 20) + 1
    ids = jnp.asarray([0, 3, n_blocks - 1, 0, -1], jnp.int32)
    cur = jnp.asarray(0, jnp.int32)
    run = lambda: observe_scatter(ids, cur, n_blocks=n_blocks, period=3,
                                  tile_m=128, use_pallas=True,
                                  interpret=interpret)
    if not interpret:
        with pytest.raises(ValueError, match="does not compile for TPU"):
            run()
        return
    h, p = run()
    h_ref, p_ref = observe_scatter_ref(ids, cur, n_blocks=n_blocks, period=3)
    np.testing.assert_array_equal(np.asarray(h), np.asarray(h_ref))
    np.testing.assert_array_equal(np.asarray(p), np.asarray(p_ref))


# ------------------------------------------------- fused observe_all parity
def test_observe_all_pallas_bit_identical_fault_free():
    rng = np.random.default_rng(2)
    n_blocks = 313
    batches = jnp.asarray(
        rng.integers(0, n_blocks, size=(3, 257)).astype(np.int32))
    b0 = tel.bundle_init(n_blocks, pebs_period=31, nb_scan_rate=17)
    b1 = tel.bundle_init(n_blocks, pebs_period=31, nb_scan_rate=17)
    r0 = tel.observe_all(b0, batches)
    r1 = tel.observe_all(b1, batches, pallas=BACKEND)
    assert _bundles_equal(r0, r1)


def test_observe_all_pallas_bit_identical_with_faults():
    """The faulty path draws its keep mask in XLA and hands it to the
    kernel; drop accounting, saturation, resets and stalls must all land
    identically."""
    rng = np.random.default_rng(3)
    n_blocks = 200
    batches = jnp.asarray(
        rng.integers(0, n_blocks, size=(4, 300)).astype(np.int32))
    fm = FaultModel.create(hmu_counter_bits=5, pebs_drop_p=0.4,
                           nb_stall_p=0.3, reset_p=0.2, seed=9,
                           n_blocks=n_blocks)
    b0 = tel.bundle_init(n_blocks, pebs_period=11, nb_scan_rate=9, faults=fm)
    b1 = tel.bundle_init(n_blocks, pebs_period=11, nb_scan_rate=9, faults=fm)
    r0 = tel.observe_all(b0, batches)
    r1 = tel.observe_all(b1, batches, pallas=BACKEND)
    assert _bundles_equal(r0, r1)
    assert int(r1.faults.pebs_dropped.lo) > 0       # faults actually fired


def test_observe_all_pallas_traces_once():
    """Swapping the kernel in must not retrace per epoch: pallas is static
    config, so repeated calls reuse one trace per (shape, backend)."""
    n_blocks = 64
    batches = jnp.zeros((2, 128), jnp.int32)
    bundle = tel.bundle_init(n_blocks, pebs_period=7, nb_scan_rate=3)
    before = tel.TRACE_COUNTS["observe_all"]
    for _ in range(3):
        bundle = tel.observe_all(bundle, batches, pallas=BACKEND)
    assert tel.TRACE_COUNTS["observe_all"] - before == 1

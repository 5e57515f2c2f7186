"""Per-kernel validation: Pallas (interpret=True) vs pure-jnp oracle, with
shape/dtype sweeps, plus hypothesis property tests on telemetry invariants."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest
from _hypothesis_compat import given, settings, st

from repro.kernels.gather_count import gather_count, gather_count_ref
from repro.kernels.embedding_bag import embedding_bag, embedding_bag_ref
from repro.kernels.flash_attention import flash_attention, attention_ref
from repro.kernels.flash_attention.kernel import flash_attention_pallas


# --------------------------------------------------------------- gather_count
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("n,d,m,block_rows", [
    (256, 128, 128, 8),
    (512, 256, 384, 16),
    (128, 512, 100, 4),     # M not a tile multiple -> padding path
])
def test_gather_count_matches_ref(n, d, m, block_rows, dtype):
    rng = np.random.default_rng(0)
    storage = jnp.asarray(rng.normal(size=(n, d)), dtype)
    idx = jnp.asarray(rng.integers(0, n, m), jnp.int32)
    counts = jnp.zeros((n // block_rows,), jnp.int32)
    out_p, c_p = gather_count(storage, idx, counts, block_rows=block_rows,
                              use_pallas=True, interpret=True, tile_m=128)
    out_r, c_r = gather_count_ref(storage, idx, counts, block_rows=block_rows)
    np.testing.assert_allclose(np.asarray(out_p, np.float32),
                               np.asarray(out_r, np.float32))
    np.testing.assert_array_equal(np.asarray(c_p), np.asarray(c_r))


@pytest.mark.parametrize("m,tile_m", [
    (1, 128),       # a single lookup: the tile is almost all padding
    (129, 128),     # one element past a tile boundary
    (127, 128),     # one element short of a tile
])
def test_gather_count_ragged_tiles_pad_correction(m, tile_m):
    """The wrapper pads ragged index tails with row 0 and subtracts the
    phantom counts afterwards — block 0's counter must come out exact even
    when padding dominates the final tile."""
    rng = np.random.default_rng(7)
    storage = jnp.asarray(rng.normal(size=(64, 128)), jnp.float32)
    idx = jnp.asarray(rng.integers(0, 64, m), jnp.int32)
    counts = jnp.full((8,), 5, jnp.int32)        # non-zero carry-in
    out_p, c_p = gather_count(storage, idx, counts, block_rows=8,
                              use_pallas=True, interpret=True, tile_m=tile_m)
    out_r, c_r = gather_count_ref(storage, idx, counts, block_rows=8)
    np.testing.assert_allclose(np.asarray(out_p), np.asarray(out_r))
    np.testing.assert_array_equal(np.asarray(c_p), np.asarray(c_r))
    assert out_p.shape == (m, 128)


def test_embedding_bag_ragged_bag_grid():
    """Bag grid that is no multiple of anything tile-ish (B=3, L=5) — the
    kernel's per-bag loop must not depend on round shapes."""
    rng = np.random.default_rng(8)
    storage = jnp.asarray(rng.normal(size=(128, 128)), jnp.float32)
    idx = jnp.asarray(rng.integers(0, 128, (3, 5)), jnp.int32)
    w = jnp.asarray(rng.uniform(0.5, 1.5, (3, 5)), jnp.float32)
    counts = jnp.zeros((16,), jnp.int32)
    out_p, c_p = embedding_bag(storage, idx, counts, w, block_rows=8,
                               use_pallas=True, interpret=True)
    out_r, c_r = embedding_bag_ref(storage, idx, w, counts, block_rows=8)
    np.testing.assert_allclose(np.asarray(out_p), np.asarray(out_r),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(c_p), np.asarray(c_r))


@pytest.mark.parametrize("kernel", ["gather_count", "embedding_bag"])
def test_compiled_request_raises_for_kernels_without_tpu_lowering(kernel):
    """Neither kernel compiles for TPU: the default is the reference on
    every platform, and asking for the compiled kernel is an error."""
    storage = jnp.ones((64, 128), jnp.float32)
    counts = jnp.zeros((8,), jnp.int32)
    if kernel == "gather_count":
        run = lambda **kw: gather_count(storage, jnp.asarray([0, 9], jnp.int32),
                                        counts, block_rows=8, **kw)
    else:
        run = lambda **kw: embedding_bag(
            storage, jnp.asarray([[0, 9]], jnp.int32), counts, block_rows=8,
            **kw)
    _, c = run()
    np.testing.assert_array_equal(np.asarray(c), [1, 1, 0, 0, 0, 0, 0, 0])
    with pytest.raises(ValueError, match="does not compile for TPU"):
        run(use_pallas=True, interpret=False)


def test_gather_count_accumulates_over_calls():
    storage = jnp.zeros((64, 128), jnp.float32)
    counts = jnp.zeros((8,), jnp.int32)
    idx = jnp.asarray([0, 8, 8, 63], jnp.int32)
    for _ in range(3):
        _, counts = gather_count(storage, idx, counts, block_rows=8,
                                 use_pallas=True, interpret=True, tile_m=128)
    expect = np.zeros(8, np.int32)
    expect[0] += 3; expect[1] += 6; expect[7] += 3
    np.testing.assert_array_equal(np.asarray(counts), expect)


@settings(max_examples=10, deadline=None)
@given(st.lists(st.integers(min_value=0, max_value=255), min_size=1, max_size=64))
def test_property_counts_equal_exact_histogram(idx_list):
    """HMU telemetry invariant: kernel counters == exact per-block histogram."""
    storage = jnp.zeros((256, 128), jnp.bfloat16)
    idx = jnp.asarray(idx_list, jnp.int32)
    counts = jnp.zeros((32,), jnp.int32)
    _, c = gather_count(storage, idx, counts, block_rows=8,
                        use_pallas=True, interpret=True, tile_m=128)
    ref = np.bincount(np.asarray(idx_list) // 8, minlength=32)
    np.testing.assert_array_equal(np.asarray(c), ref)


# -------------------------------------------------------------- embedding_bag
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,l,n,d,block_rows", [
    (4, 8, 256, 128, 8),
    (8, 16, 512, 256, 16),
    (2, 4, 128, 512, 4),
])
def test_embedding_bag_matches_ref(b, l, n, d, block_rows, dtype):
    rng = np.random.default_rng(1)
    storage = jnp.asarray(rng.normal(size=(n, d)), dtype)
    idx = jnp.asarray(rng.integers(0, n, (b, l)), jnp.int32)
    w = jnp.asarray(rng.uniform(0.5, 1.5, (b, l)), jnp.float32)
    counts = jnp.zeros((n // block_rows,), jnp.int32)
    out_p, c_p = embedding_bag(storage, idx, counts, w, block_rows=block_rows,
                               use_pallas=True, interpret=True)
    out_r, c_r = embedding_bag_ref(storage, idx, w, counts, block_rows=block_rows)
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(np.asarray(out_p, np.float32),
                               np.asarray(out_r, np.float32), rtol=tol, atol=tol)
    np.testing.assert_array_equal(np.asarray(c_p), np.asarray(c_r))


def test_embedding_bag_unweighted_defaults_to_sum():
    storage = jnp.eye(16, 128, dtype=jnp.float32)
    idx = jnp.asarray([[0, 1, 2, 3]], jnp.int32)
    counts = jnp.zeros((4,), jnp.int32)
    out, _ = embedding_bag(storage, idx, counts, block_rows=4,
                           use_pallas=True, interpret=True)
    expect = np.zeros((1, 128), np.float32)
    expect[0, :4] = 1.0
    np.testing.assert_allclose(np.asarray(out), expect)


# ------------------------------------------------------------ flash_attention
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("bh,kvh,s,d", [
    (4, 4, 256, 128),      # MHA
    (8, 2, 256, 128),      # GQA 4:1
    (2, 1, 512, 256),      # MQA
])
def test_flash_attention_causal_matches_ref(bh, kvh, s, d, dtype):
    rng = np.random.default_rng(2)
    q = jnp.asarray(rng.normal(size=(bh, s, d)) * 0.3, dtype)
    k = jnp.asarray(rng.normal(size=(kvh, s, d)) * 0.3, dtype)
    v = jnp.asarray(rng.normal(size=(kvh, s, d)) * 0.3, dtype)
    out_p = flash_attention_pallas(q, k, v, q_per_kv=bh // kvh, causal=True,
                                   interpret=True)
    out_r = attention_ref(q, k, v, q_per_kv=bh // kvh, causal=True)
    tol = 3e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out_p, np.float32),
                               np.asarray(out_r, np.float32), rtol=tol, atol=tol)


def test_flash_attention_sliding_window():
    rng = np.random.default_rng(3)
    q = jnp.asarray(rng.normal(size=(2, 512, 128)) * 0.3, jnp.float32)
    k = jnp.asarray(rng.normal(size=(2, 512, 128)) * 0.3, jnp.float32)
    v = jnp.asarray(rng.normal(size=(2, 512, 128)) * 0.3, jnp.float32)
    out_p = flash_attention_pallas(q, k, v, q_per_kv=1, causal=True, window=128,
                                   interpret=True)
    out_r = attention_ref(q, k, v, q_per_kv=1, causal=True, window=128)
    np.testing.assert_allclose(np.asarray(out_p), np.asarray(out_r),
                               rtol=2e-5, atol=2e-5)


def test_flash_attention_noncausal():
    rng = np.random.default_rng(4)
    q = jnp.asarray(rng.normal(size=(2, 256, 128)) * 0.3, jnp.float32)
    k = jnp.asarray(rng.normal(size=(2, 256, 128)) * 0.3, jnp.float32)
    v = jnp.asarray(rng.normal(size=(2, 256, 128)) * 0.3, jnp.float32)
    out_p = flash_attention_pallas(q, k, v, q_per_kv=1, causal=False, interpret=True)
    out_r = attention_ref(q, k, v, q_per_kv=1, causal=False)
    np.testing.assert_allclose(np.asarray(out_p), np.asarray(out_r),
                               rtol=2e-5, atol=2e-5)


def test_flash_attention_wrapper_fallback_on_cpu():
    # wrapper should silently use the oracle on CPU (no TPU available here)
    q = jnp.ones((2, 128, 128), jnp.float32)
    out = flash_attention(q, q, q, q_per_kv=1)
    assert out.shape == (2, 128, 128)
    assert not np.any(np.isnan(np.asarray(out)))

"""hist_select: one-pass radix-histogram threshold select vs its oracles.

The kernel's contract is bit-identity with selectk's 32-round bitwise
threshold search — and therefore with the lax.top_k-equivalent selection
built on it, including lowest-index tie-breaks and the int32.min quota
sentinel.  Everything runs through the Pallas interpreter so CPU CI
executes the actual kernel body, not just the jnp reference."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import selectk
from repro.kernels.dispatch import XLA, PallasBackend
from repro.kernels.hist_select import MAX_N, kth_key_u, kth_key_u_ref

BACKEND = PallasBackend(interpret=True, select_tile_n=512)


def _keys(rng, n, b=1, ties=True):
    u = rng.integers(0, np.iinfo(np.uint32).max, size=(b, n), dtype=np.uint32)
    if ties and n >= 8:
        u[:, : n // 4] = u[:, 0:1]          # long duplicate run
    return jnp.asarray(u)


# ----------------------------------------------------- threshold bit-identity
@pytest.mark.parametrize("n", [50, 130, 997, 2048])
def test_kth_key_matches_ref_and_bitwise_search(n):
    rng = np.random.default_rng(0)
    u = _keys(rng, n, b=3)
    seg = jnp.zeros((n,), jnp.int32)
    for k in {0, 1, 7, n // 2, n}:
        t_pal = kth_key_u(u, seg, (k,), tile_n=BACKEND.select_tile_n,
                          use_pallas=True, interpret=True)
        t_ref = kth_key_u_ref(u, seg, (k,))
        t_bit = selectk._kth_largest(u, k)
        np.testing.assert_array_equal(np.asarray(t_pal),
                                      np.asarray(t_ref), err_msg=f"k={k}")
        np.testing.assert_array_equal(np.asarray(t_pal).reshape(-1),
                                      np.asarray(t_bit).reshape(-1),
                                      err_msg=f"k={k}")


@pytest.mark.parametrize("entry", ["kernel", "selectk_kernel", "selectk_xla"])
def test_kth_key_rejects_oversized_input(entry):
    """Past MAX_N the f32 counts are no longer exact: the kernel and a
    backend that selects it raise, and only a backend that resolved the
    select site to XLA takes the 32-round search."""
    n = MAX_N + 1
    u = jnp.zeros((1, n), jnp.uint32)
    seg = jnp.zeros((n,), jnp.int32)
    if entry == "selectk_xla":
        t = selectk._kth_dispatch(u, 1, XLA)
        np.testing.assert_array_equal(np.asarray(t), [0])
        return
    with pytest.raises(ValueError, match="MAX_N"):
        if entry == "kernel":
            kth_key_u(u, seg, (1,), use_pallas=True, interpret=True)
        else:
            selectk._kth_dispatch(u, 1, BACKEND)


# -------------------------------------------- selection entry-point parity
@pytest.mark.parametrize("n,k", [(997, 97), (130, 13), (2048, 256)])
def test_select_top_k_backend_matches_lax_top_k(n, k):
    rng = np.random.default_rng(1)
    x = rng.integers(0, 5, n).astype(np.int32)      # tie-heavy
    # quota-masked rows carry int32.min sentinels; they must never select
    x[rng.choice(n, n // 10, replace=False)] = np.iinfo(np.int32).min
    xj = jnp.asarray(x)
    v_ref, i_ref = jax.lax.top_k(xj, k)
    v0, i0, m0 = selectk.select_top_k(xj, k, return_mask=True)
    v1, i1, m1 = selectk.select_top_k(xj, k, return_mask=True,
                                      backend=BACKEND)
    np.testing.assert_array_equal(np.asarray(v_ref), np.asarray(v1))
    np.testing.assert_array_equal(np.asarray(i_ref), np.asarray(i1))
    np.testing.assert_array_equal(np.asarray(m0), np.asarray(m1))
    np.testing.assert_array_equal(
        np.asarray(selectk.top_k_mask(xj, k)),
        np.asarray(selectk.top_k_mask(xj, k, backend=BACKEND)))


def test_segment_top_k_mask_backend_matches_per_slice():
    """Per-tenant quota select: the vectorized kernel path must reproduce
    the per-slice XLA path bit for bit — zero-cap tenants (nothing
    protected) and over-sized caps (everything protected) included."""
    rng = np.random.default_rng(2)
    n = 997
    bounds = (0, 137, 400, n)
    caps = (10, 0, 900)
    x = jnp.asarray(rng.integers(0, 7, (2, n)).astype(np.int32))
    m0 = selectk.segment_top_k_mask(x, bounds, caps)
    m1 = selectk.segment_top_k_mask(x, bounds, caps, backend=BACKEND)
    np.testing.assert_array_equal(np.asarray(m0), np.asarray(m1))
    # cap semantics hold on the kernel path too
    got = np.asarray(m1)
    for s, (lo, hi) in enumerate(zip(bounds[:-1], bounds[1:])):
        counts = got[:, lo:hi].sum(axis=-1)
        assert (counts == min(caps[s], hi - lo)).all()


# --------------------------------------------------------------- satellites
def test_prefix_sum_prime_sizes_match_cumsum():
    """Regression: prefix_sum used to silently fall back to one jnp.cumsum
    whenever chunk didn't divide n — prime sizes now pad to the chunked
    scan and must still be exact."""
    rng = np.random.default_rng(3)
    for n in (1, 7, 97, 257, 1009):
        x = jnp.asarray((rng.random((2, n)) < 0.5))
        np.testing.assert_array_equal(
            np.asarray(jnp.cumsum(x.astype(jnp.int32), axis=-1)),
            np.asarray(selectk.prefix_sum(x)), err_msg=f"n={n}")


def test_sortable_key_contract_checked_and_sentinels_order_low():
    """sortable_key's precondition — non-negative scores, or all negatives
    equal to one shared sentinel — is debug-asserted eagerly; the two
    sentinels the runtime actually uses (float -1 demotion marker,
    int32.min quota mask) must order below every real score."""
    ok = selectk.sortable_key(jnp.asarray([3.0, 0.0, -1.0, -1.0]))
    u = np.asarray(selectk._to_u(ok))
    assert (u[2] == u[3]) and (u[2] < u[0]) and (u[2] < u[1])
    q = np.asarray(selectk._to_u(
        jnp.asarray([5, 0, np.iinfo(np.int32).min], jnp.int32)))
    assert q[2] < q[1] < q[0]
    with pytest.raises(ValueError, match="sentinel"):
        selectk.sortable_key(jnp.asarray([1.0, -1.0, -2.0]))
    # tracers can't be inspected eagerly — the check must not fire under jit
    jax.jit(selectk.sortable_key)(
        jnp.asarray([1.0, -1.0, -2.0])).block_until_ready()

"""Kernel dispatch policy + fused-runtime integration of the Pallas
telemetry kernels (hist_select / observe_scatter).

The runtime promise: ``use_pallas=True`` changes the *implementation* of
the selection and observe scatters, never a bit of the results, and the
epoch loop still costs exactly 2 dispatches and one trace."""
import numpy as np
import jax
import pytest

from repro.core import runtime as rt
from repro.faults.model import FaultModel
from repro.kernels import dispatch
from repro.kernels.dispatch import PallasBackend, resolve_backend


_MAX_N = dispatch.MAX_N
_BOTH_INTERPRET = ("interpret", "hist_select", "observe_scatter")
_TPU_DEFAULT = ("compiled", "hist_select", "xla")
_XLA = ("compiled", "xla", "xla")


@pytest.mark.parametrize("platform,kw,want", [
    # off TPU: XLA unless asked; asked -> both kernel bodies, interpreted
    ("cpu", dict(), _XLA),
    ("cpu", dict(use_pallas=False), _XLA),
    ("cpu", dict(use_pallas=True), _BOTH_INTERPRET),
    ("cpu", dict(use_pallas=True, n_blocks=_MAX_N + 1), "MAX_N"),
    ("cpu", dict(use_pallas=True, sharded=True), "sharded"),
    ("cpu", dict(n_blocks=5_000_000), _XLA),
    # on TPU: compiled hist_select where it is exact and unsharded; the
    # scatter kernel has no TPU lowering, so the scatter site is XLA
    ("tpu", dict(), _TPU_DEFAULT),
    ("tpu", dict(n_blocks=5_000_000), _TPU_DEFAULT),
    ("tpu", dict(n_blocks=_MAX_N + 1), _XLA),
    ("tpu", dict(sharded=True), _XLA),
    ("tpu", dict(use_pallas=False), _XLA),
    ("tpu", dict(use_pallas=True), _TPU_DEFAULT),
    ("tpu", dict(use_pallas=True, n_blocks=_MAX_N + 1), "MAX_N"),
    ("tpu", dict(use_pallas=True, sharded=True), "sharded"),
    ("tpu", dict(n_blocks=_MAX_N), _TPU_DEFAULT),
])
def test_resolve_backend_policy_off_tpu(monkeypatch, platform, kw, want):
    """Every decision is visible in the resolved backend, and an explicit
    request that cannot be honoured raises (the platform is steered here,
    in the test; the suite itself runs on CPU)."""
    assert jax.default_backend() != "tpu"
    monkeypatch.setattr(dispatch, "_platform", lambda: platform)
    kw = {"n_blocks": 5_000, **kw}
    if isinstance(want, str):
        with pytest.raises(ValueError, match=want):
            resolve_backend(**kw)
        return
    b = resolve_backend(**kw)
    assert isinstance(b, PallasBackend)
    assert ("interpret" if b.interpret else "compiled",
            b.select, b.scatter) == want
    assert resolve_backend(**kw, select_tile_n=256).select_tile_n == 256


def test_runtime_rejects_pallas_with_mesh_or_reference_path():
    with pytest.raises(ValueError, match="mesh"):
        rt.EpochRuntime(64, 8, use_pallas=True, mesh=object())
    with pytest.raises(ValueError, match="fused"):
        rt.EpochRuntime(64, 8, use_pallas=True, fused=False)
    # default off TPU: no kernels, no error, and the decision is recorded
    assert rt.EpochRuntime(64, 8).kernels == {"select": "xla",
                                              "scatter": "xla"}


def _run(n, k, eps, use_pallas, **kw):
    run = rt.EpochRuntime(n, k, policies=("hmu_oracle", "hinted",
                                          "nb_two_touch"),
                          pebs_period=7, nb_scan_rate=n // 4, fused=True,
                          use_pallas=use_pallas, **kw)
    with rt.counting() as c:
        for e in eps:
            run.step(e)
        disp = c.dispatch["observe_all"] + c.dispatch["epoch_step"]
        traces = c.trace["epoch_step"]
    return run, disp / len(eps), traces


@pytest.mark.parametrize("variant", ["plain", "quotas", "faults"])
def test_fused_runtime_pallas_bit_identical_two_dispatches(variant):
    rng = np.random.default_rng(11)
    n, k, n_epochs = 256, 32, 3
    eps = [(rng.zipf(1.3, size=(2, 1024)) % n).astype(np.int32)
           for _ in range(n_epochs)]
    kw = {}
    if variant == "quotas":
        kw["tenancy"] = rt.Tenancy(offsets=(0, 100, n), hot_k=(8, 8),
                                   caps=(8, 16))
    elif variant == "faults":
        kw["faults"] = FaultModel.create(hmu_counter_bits=9,
                                         pebs_drop_p=0.25, nb_stall_p=0.2,
                                         seed=11, n_blocks=n)
    off, _, _ = _run(n, k, eps, use_pallas=False, **kw)
    on, disp, traces = _run(n, k, eps, use_pallas=True, **kw)
    assert on.kernels == {"select": "hist_select (interpret)",
                          "scatter": "observe_scatter (interpret)"}
    assert disp == 2 and traces <= 1
    for lane in off.records:
        assert [a.to_dict() for a in off.records[lane]] \
            == [b.to_dict() for b in on.records[lane]], lane
        np.testing.assert_array_equal(
            np.asarray(off.lanes[lane].slot_to_block),
            np.asarray(on.lanes[lane].slot_to_block))
    if variant == "quotas":
        for ra, rb in zip(off.tenant_records, on.tenant_records):
            for key in ra:
                np.testing.assert_array_equal(ra[key], rb[key], err_msg=key)

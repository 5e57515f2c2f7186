"""`correct` separates a sound run from a broken one, at a reduced size.

Each cell runs through the harness (device check skipped) at 20,000
blocks; the sound run must compare equal to the plain reference, and every
fault a one-chip cell can have must come out as not correct:

* the control -- the harness module's reference computed with bfloat16
  scores, put in the program's place;
* an epoch step that returns its state unchanged;
* half of every epoch's ids left out of the observe;
* one record altered where the runtime assembles it.

The exchange between chips does not exist in a one-chip cell.
"""
import functools

import pytest

import run_cell

CELLS = ("dlrm_paper.hinted", "mmap_paper.scan")
SEED = 2_718_281_828_459      # larger than 32 bits hold


@pytest.fixture(scope="module", params=CELLS)
def spec(request):
    return run_cell.load_cell(request.param, run_cell.REHEARSAL_BLOCKS)


def _run(spec, **kw):
    return run_cell.run(spec, SEED, 1.0, False, require_tpu=False, **kw)


def test_sound_run_is_correct(spec):
    r = _run(spec)
    assert r["correct"] is True
    assert r["failed"] == 0 and r["attempted"] > 0
    assert list(r)[-1] == "checks"
    assert all(c["value"] == 0 for c in r["checks"].values())


def test_control_is_not_correct(spec):
    r = _run(spec, reference=functools.partial(spec["harness"].reference,
                                               control=True))
    assert r["correct"] is False
    assert r["checks"]["record_mismatches"]["value"] > 0


def _unchanged_step(monkeypatch):
    from repro.core import runtime
    monkeypatch.setattr(runtime, "_epoch_step",
                        lambda state, *a, **k: state)


def _half_batch(monkeypatch):
    from repro.core import telemetry
    orig = telemetry.observe_all

    def observe_half(bundle, batches, pallas=None):
        return orig(bundle, batches[:, : batches.shape[1] // 2],
                    pallas=pallas)
    monkeypatch.setattr(telemetry, "observe_all", observe_half)


def _altered_record(monkeypatch):
    from repro.core.runtime import EpochRuntime
    orig = EpochRuntime._record

    def record(self, name, epoch, *a, **k):
        rec = orig(self, name, epoch, *a, **k)
        if name == "hinted" and epoch == 5:
            rec.promoted += 1
        return rec
    monkeypatch.setattr(EpochRuntime, "_record", record)


@pytest.mark.parametrize("fault", [_unchanged_step, _half_batch,
                                   _altered_record])
def test_fault_is_not_correct(spec, monkeypatch, fault):
    fault(monkeypatch)
    r = _run(spec)
    assert r["correct"] is False
    assert r["failed"] > 0

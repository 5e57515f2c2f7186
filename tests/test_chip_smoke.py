"""chip_smoke.py off the chip: it refuses to run on the CPU, places the
compile cache as documented, and its phases run end to end at reduced
sizes (the ``--rehearse`` path: CPU, interpreted kernels)."""
import dataclasses
import json
import sys
from pathlib import Path

import jax
import pytest

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

import chip_smoke  # noqa: E402
from repro import compile_cache  # noqa: E402

TINY = dataclasses.replace(chip_smoke.REHEARSAL_SPEC, n_params=5_120_000,
                           lookups_per_batch=2_400)


def test_refuses_without_tpu(capsys):
    assert jax.default_backend() != "tpu"
    assert chip_smoke.main([]) == 2
    out = capsys.readouterr()
    assert out.out == "" and "no TPU" in out.err


@pytest.mark.parametrize("placed", [None, "/elsewhere/cache"])
def test_compile_cache_placement(monkeypatch, placed):
    was = jax.config.jax_compilation_cache_dir
    if placed is None:
        monkeypatch.delenv(compile_cache.ENV, raising=False)
    else:
        monkeypatch.setenv(compile_cache.ENV, placed)
    try:
        got = compile_cache.use_compile_cache(REPO)
        if placed is None:
            assert got == str(REPO / ".jax_cache")
            assert jax.config.jax_compilation_cache_dir == got
        else:
            # JAX reads the variable itself; nothing else is set in code
            assert got == placed
            assert jax.config.jax_compilation_cache_dir == was
    finally:
        jax.config.update("jax_compilation_cache_dir", was)


def test_paper_phase_rehearsal(capsys):
    chip_smoke.phase_b(TINY, chip_smoke.CPU_KERNELS, use_pallas=True)
    out = capsys.readouterr().out
    assert '"dispatches_per_epoch": 2.0' in out


def test_sharded_phase_rehearsal_on_one_device(capsys):
    chip_smoke.phase_c(TINY, n_devices=1)
    out = capsys.readouterr().out
    assert "bit-identical" in out
    # the result line is main()'s alone: phases never print it
    assert not any(line.startswith("{") and json.loads(line).get("ok")
                   for line in out.splitlines())

"""Ahead-of-time compiles of the default TPU path for a described v5e chip.

Interpret mode runs a kernel's body but not the TPU compiler, which is what
refuses block shapes that break the (8, 128) tiling rule, primitives with
no Mosaic lowering, and programs that do not fit the chip.  These tests
compile — nothing runs — every kernel left on the default TPU path at the
sizes the runtime uses, plus the fused epoch programs at paper-scale DLRM
(5,000,000 pages) and at the two-tenant fleet cell (7,621,440 pages under
weighted-fair quotas), for one chip of a ``v5e:2x2`` topology.

The topology is described inside a module-scoped fixture, never while the
module is imported: only one process at a time may load the TPU library,
and every test worker imports this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest

from repro.core import runtime as rt
from repro.core import telemetry as tel
from repro.dlrm import datagen
from repro.kernels.dispatch import PallasBackend, resolve_backend
from repro.kernels.hist_select import kth_key_u
from repro.scenarios import DLRMScenario

V5E_HBM_BYTES = 16 * 1024 ** 3
# the backend resolve_backend gives a TPU run: compiled hist_select, XLA
# scatters (observe_scatter has no TPU lowering)
TPU_BACKEND = PallasBackend(interpret=False, select="hist_select",
                            scatter="xla")


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_cache():
    # a compile for a described chip is written to a persistent cache but
    # cannot be read back without one; keep these compiles out of it
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def test_tpu_backend_is_what_resolve_gives(monkeypatch):
    from repro.kernels import dispatch
    monkeypatch.setattr(dispatch, "_platform", lambda: "tpu")
    assert resolve_backend(n_blocks=datagen.PAPER.n_pages) == TPU_BACKEND


@pytest.mark.parametrize("n_segments", [1, 4])
@pytest.mark.parametrize("n", [5_000, 1_048_576])
def test_hist_select_compiles(one_chip, no_cache, n, n_segments):
    """B=6 key rows (the fused step's unique selection signals), one global
    segment or four tenant segments."""
    ks = tuple(range(1, n_segments + 1))
    f = jax.jit(lambda u, seg: kth_key_u(
        u, seg, ks, tile_n=TPU_BACKEND.select_tile_n, use_pallas=True,
        interpret=False))
    compiled = f.lower(_spec((6, n), jnp.uint32, one_chip),
                       _spec((n,), jnp.int32, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()


# the two-tenant fleet cell (bench/configs/fleet_paper.json): DLRM's
# 5,000,000 pages then mmap-bench's 2,621,440, capped at their hot sets
FLEET_OFFSETS = (0, 5_000_000, 7_621_440)
FLEET_CAPS = (486_587, 262_144)


def test_hist_select_compiles_at_the_fleet_cell(one_chip, no_cache):
    """The fleet's segment-capped select: B=5 unique key rows over
    7,621,440 blocks in two tenant segments."""
    n = FLEET_OFFSETS[-1]
    f = jax.jit(lambda u, seg: kth_key_u(
        u, seg, FLEET_CAPS, tile_n=TPU_BACKEND.select_tile_n,
        use_pallas=True, interpret=False))
    compiled = f.lower(_spec((5, n), jnp.uint32, one_chip),
                       _spec((n,), jnp.int32, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _runtime_shapes(one_chip, make):
    """``make()``'s runtime and its fused state, as shapes on the
    described chip: the constructor runs under ``eval_shape``, so nothing
    of paper size is allocated here."""
    box = {}

    def build():
        box["rt"] = run = make()
        return run._state

    state = jax.eval_shape(build)
    state = jax.tree_util.tree_map(
        lambda s: _spec(s.shape, s.dtype, one_chip), state)
    return box["rt"], state


def _paper_runtime_shapes(one_chip):
    return _runtime_shapes(one_chip, lambda: rt.EpochRuntime.for_scenario(
        DLRMScenario(spec=datagen.PAPER), use_pallas=False, sync_every=2))


def _compile_epoch_and_check_fit(one_chip, run, state, batch_shape):
    """observe_all and _epoch_step compiled for the chip on the TPU
    backend: the select kernel is in the step, and each fits the chip."""
    batches = _spec(batch_shape, jnp.int32, one_chip)
    observe = tel.observe_all.lower(state.bundle, batches,
                                    pallas=TPU_BACKEND).compile()
    bound = batch_shape[0] * batch_shape[1] // state.bundle.pebs.period + 2
    s_max = min(run.n_blocks, 1 << (bound - 1).bit_length())
    scalar = _spec((), jnp.int32, one_chip)
    step = rt._epoch_step.lower(
        state, scalar, scalar, cfg=run._cfg._replace(pallas=TPU_BACKEND),
        s_max=s_max).compile()
    assert "tpu_custom_call" in step.as_text()      # hist_select is in it
    for compiled in (observe, step):
        m = compiled.memory_analysis()
        need = (m.argument_size_in_bytes + m.output_size_in_bytes
                - m.alias_size_in_bytes + m.temp_size_in_bytes)
        assert 0 < need < V5E_HBM_BYTES, need


def test_paper_scale_fused_epoch_compiles_and_fits(one_chip, no_cache):
    spec = datagen.PAPER
    run, state = _paper_runtime_shapes(one_chip)
    assert run.n_blocks == spec.n_pages == 5_000_000
    _compile_epoch_and_check_fit(one_chip, run, state,
                                 (4, spec.lookups_per_batch))


def test_fleet_scale_fused_epoch_compiles_and_fits(one_chip, no_cache):
    """The fleet cell's epoch: weighted-fair quotas over both tenants,
    four interleaved rows of 4,497,152 accesses."""
    run, state = _runtime_shapes(one_chip, lambda: rt.EpochRuntime(
        FLEET_OFFSETS[-1], sum(FLEET_CAPS), pebs_period=401,
        nb_scan_rate=FLEET_OFFSETS[-1] // 4, use_pallas=False,
        tenancy=rt.Tenancy(offsets=FLEET_OFFSETS, hot_k=FLEET_CAPS,
                           caps=FLEET_CAPS)))
    assert run._cfg.tenancy.caps == FLEET_CAPS
    _compile_epoch_and_check_fit(one_chip, run, state, (4, 4_497_152))

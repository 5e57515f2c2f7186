"""Shared Pallas dispatch policy for the telemetry kernels.

Every kernel package in ``repro.kernels`` follows one triad — ``ref.py`` (the
pure-jnp oracle), ``kernel.py`` (the Pallas kernel), ``ops.py`` (a jit'd
wrapper) — and the *core* integration points (``selectk`` / ``telemetry`` /
``runtime``) take one resolved :class:`PallasBackend`: hashable static jit
config (it rides in ``runtime._FusedCfg``) that records which
implementation each kernel site runs at the runtime's size.

Two sites exist:

* ``select`` — the k-th-largest threshold under every top-k selection:
  ``"hist_select"`` (the radix-histogram kernel) or ``"xla"`` (selectk's
  32-round bitwise search).  The kernel's f32 histogram counts are exact
  below ``hist_select.MAX_N`` elements, so larger block spaces take XLA.
* ``scatter`` — the per-batch collector scatters in ``observe_all``:
  ``"observe_scatter"`` (the fused scatter kernel) or ``"xla"``.  The
  kernel does not compile for TPU (its per-id read-modify-write of a VMEM
  histogram is a scalar VMEM store, which Mosaic refuses), so it runs only
  in interpret mode, as an off-TPU parity check.

Resolution rule (:func:`resolve_backend`):

* ``use_pallas=None`` (default) — on TPU, ``select`` is ``hist_select``
  where ``n_blocks <= MAX_N`` and the state is not sharded; ``scatter`` is
  ``xla``.  Off TPU, both are ``xla``.
* ``use_pallas=True`` — every kernel the platform can run, or an error:
  on TPU, compiled ``hist_select`` (``scatter`` stays ``xla``); off TPU,
  both kernels in ``interpret=True`` mode (Pallas's CPU interpreter), the
  CI parity path.  A size past ``MAX_N`` or a sharded state raises.
* ``use_pallas=False`` — XLA everywhere (the bit-identity oracle).

The mode follows the platform: a kernel never runs in interpret mode on a
TPU, and never compiled off one.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax

from .hist_select import MAX_N

__all__ = ["PallasBackend", "XLA", "resolve_backend"]


class PallasBackend(NamedTuple):
    """Static (hashable) kernel-dispatch config baked into jit traces.

    ``interpret``       — run kernels through the Pallas interpreter (CPU
                          parity mode) instead of compiling for TPU.
    ``select``          — ``"hist_select"`` or ``"xla"``.
    ``scatter``         — ``"observe_scatter"`` or ``"xla"``.
    ``select_tile_n``   — hist_select: key elements per grid tile.
    ``scatter_tile_m``  — observe_scatter: id-stream elements per grid tile.
    """
    interpret: bool = False
    select: str = "hist_select"
    scatter: str = "observe_scatter"
    select_tile_n: int = 2048
    scatter_tile_m: int = 1024

    @property
    def uses_select_kernel(self) -> bool:
        return self.select == "hist_select"

    @property
    def uses_scatter_kernel(self) -> bool:
        return self.scatter == "observe_scatter"

    def describe(self) -> dict:
        """The implementation of each site, as reports print it."""
        mode = "interpret" if self.interpret else "compiled"

        def name(impl: str) -> str:
            return impl if impl == "xla" else f"{impl} ({mode})"
        return {"select": name(self.select), "scatter": name(self.scatter)}


XLA = PallasBackend(select="xla", scatter="xla")


def _platform() -> str:
    return jax.default_backend()


def resolve_backend(use_pallas: Optional[bool] = None, *,
                    n_blocks: int, sharded: bool = False,
                    **overrides) -> PallasBackend:
    """The implementation of each kernel site for an ``n_blocks`` state
    (``sharded``: spread over a mesh, where the single-core kernels do not
    run).  Raises where an explicit request cannot be honoured."""
    on_tpu = _platform() == "tpu"
    select_ok = n_blocks <= MAX_N and not sharded
    if use_pallas is None:
        use_pallas = on_tpu and select_ok
    if not use_pallas:
        return XLA._replace(**overrides)
    if not select_ok:
        why = ("the state is sharded over a mesh" if sharded else
               f"n_blocks={n_blocks} exceeds hist_select.MAX_N={MAX_N}")
        raise ValueError(f"use_pallas=True cannot be honoured: {why}")
    return PallasBackend(
        interpret=not on_tpu, select="hist_select",
        scatter="xla" if on_tpu else "observe_scatter", **overrides)

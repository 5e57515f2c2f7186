"""observe_scatter — fused telemetry scatter for the epoch observe path.

One tiled pass over a batch's block-id stream yields the two histograms
every collector update in ``telemetry.observe_all`` is an affine function
of: the full access histogram (HMU saturating add, NB touched set,
true-count add) and the PEBS-sampled histogram (the in-kernel
``(cursor + position) % period`` sampler, optionally masked by a fault
model's per-event keep draw) — one read of the id stream feeding all four
collectors, replacing their four per-batch scatters.  It runs only in
interpret mode (see ``ops``); every compiled path uses the XLA scatters.
"""
from .ops import observe_scatter
from .ref import observe_scatter_ref

__all__ = ["observe_scatter", "observe_scatter_ref"]

"""Top-k routed Mixture-of-Experts with capacity dropping + expert telemetry.

Two dispatch formulations:

* ``groups=(1, 1)`` (default, single-device/tests): global sort/scatter
  dispatch — simple, exact, no sharding assumptions.
* ``groups=(gd, gm)`` + ``expert_sharded`` (set by the launcher): the
  dispatch/expert/combine interior runs under **shard_map** with explicit
  all-to-alls on the model axis — exactly the routed-token bytes cross the
  wire (the EP communication floor).  Tokens enter sequence-sharded over
  "model" (and batch-sharded over the data axes); routing, slot assignment
  and the scatter are device-local.

  History (EXPERIMENTS.md §Perf): the naive global scatter formulation let
  GSPMD replicate the (E, C, D) dispatch buffer (62 TB collective wire
  bytes/device on kimi-k2 train_4k); a pure-with_sharding_constraint
  regrouping (A1) made backward resharding WORSE (290 TB, "involuntary full
  rematerialization").  Explicit collectives are the reliable contract.

Formulated with scatter/gather (not one-hot dispatch einsums) so the HLO
contains only true expert FLOPs.

Expert activation counters come out of the router for free — the MoE
analogue of the paper's HMU telemetry (the router *is* a memory-side access
monitor for expert weights), feeding the expert tiering manager.
"""
from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp


def expert_access_batch(counts) -> np.ndarray:
    """Router telemetry -> the tiering runtime's access-batch format.

    ``counts`` is the ``aux["counts"]`` expert-activation histogram from
    :func:`moe_block` — ``(E,)`` for one layer or ``(L, E)`` stacked by the
    forward scan (layers are summed: expert banks are placed per expert id,
    one block spanning its weights in every layer).  Returns a flat int32
    stream of expert ids with multiplicity — the per-batch access stream an
    :class:`~repro.core.runtime.EpochRuntime` epoch stacks.  Its length is
    ``tokens * top_k * n_layers`` regardless of how routing is distributed,
    so every batch in an epoch has equal size by construction."""
    c = np.asarray(counts)
    if c.ndim == 2:
        c = c.sum(0)
    if c.ndim != 1:
        raise ValueError(f"counts must be (E,) or (L, E), got {c.shape}")
    return np.repeat(np.arange(c.shape[0], dtype=np.int32), c)


class MoEParams(NamedTuple):
    router: jax.Array          # (D, E)
    w_gate: jax.Array          # (E, D, Fe)
    w_up: jax.Array            # (E, D, Fe)
    w_down: jax.Array          # (E, Fe, D)
    shared_w_gate: Optional[jax.Array]  # (D, Fs) or None
    shared_w_up: Optional[jax.Array]
    shared_w_down: Optional[jax.Array]


def _constrain(x, spec_axes):
    if spec_axes is None:
        return x
    from jax.sharding import PartitionSpec as P
    return jax.lax.with_sharding_constraint(x, P(*spec_axes))


def _dispatch_local(xf, tope, topw, e, capacity, dtype):
    """Device-local slot assignment + scatter.  xf: (T, D); returns
    (x_buf (E, C, D), pos (T*k,), flat_e, dropped mask)."""
    t, d = xf.shape
    k = tope.shape[-1]
    flat_e = tope.reshape(t * k)
    order = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[order]
    first_occ = jnp.searchsorted(sorted_e, jnp.arange(e), side="left")
    pos_sorted = jnp.arange(t * k) - first_occ[sorted_e]
    pos = jnp.zeros((t * k,), jnp.int32).at[order].set(
        pos_sorted.astype(jnp.int32))
    token_of = jnp.arange(t * k) // k
    x_buf = jnp.zeros((e, capacity, d), dtype)
    x_buf = x_buf.at[flat_e, pos].set(xf[token_of], mode="drop")
    return x_buf, pos, flat_e


def _combine_local(y_buf, pos, flat_e, topw, capacity, d, dtype):
    t_k = pos.shape[0]
    k = topw.shape[-1]
    dropped = pos >= capacity
    safe_pos = jnp.minimum(pos, capacity - 1)
    y = y_buf[flat_e, safe_pos]
    y = jnp.where(dropped[:, None], 0.0, y)
    y = y.reshape(t_k // k, k, d) * topw.reshape(t_k // k, k, 1).astype(dtype)
    return y.sum(1)


def _expert_ffn(x_buf, wg, wu, wd, dtype):
    g = jnp.einsum("ecd,edf->ecf", x_buf, wg.astype(dtype))
    u = jnp.einsum("ecd,edf->ecf", x_buf, wu.astype(dtype))
    return jnp.einsum("ecf,efd->ecd", jax.nn.silu(g) * u, wd.astype(dtype))


def moe_block(
    x: jax.Array,              # (B, S, D)
    p: MoEParams,
    *,
    top_k: int,
    capacity_factor: float = 1.25,
    router_dtype=jnp.float32,
    groups: Tuple[int, int] = (1, 1),
    batch_axes=None,           # mesh axes of the token dim ("pod","data")...
    expert_sharded: bool = False,  # experts sharded over "model" (EP)?
):
    """Returns (out (B,S,D), aux dict with:
         counts  (E,) int32 — expert activation telemetry (HMU feed)
         aux_loss scalar    — switch-style load-balance loss
    """
    b, s, d = x.shape
    e = p.router.shape[1]
    gd, gm = groups
    t = b * s
    dtype = x.dtype

    # ---- router (global einsum; tiny) + telemetry + balance loss
    logits = jnp.einsum("bsd,de->bse", x.astype(router_dtype),
                        p.router.astype(router_dtype))
    probs = jax.nn.softmax(logits, axis=-1)
    topw, tope = jax.lax.top_k(probs, top_k)              # (B,S,k)
    topw = topw / jnp.clip(topw.sum(-1, keepdims=True), 1e-9)
    counts = jnp.zeros((e,), jnp.int32).at[tope.reshape(-1)].add(1)
    f_e = counts.astype(jnp.float32) / jnp.maximum(t * top_k, 1)
    aux_loss = e * jnp.sum(jax.lax.stop_gradient(f_e) * probs.mean((0, 1)))
    aux = {"counts": counts, "aux_loss": aux_loss}

    if gd * gm > 1 and expert_sharded:
        out = _moe_shard_map(x, p, tope, topw, top_k, capacity_factor,
                             groups, batch_axes)
        return out, aux

    # ---- single-program path (tests / replicated experts)
    capacity = max(int(t * top_k * capacity_factor / e), 4)
    x_buf, pos, flat_e = _dispatch_local(
        x.reshape(t, d), tope.reshape(t, top_k), topw.reshape(t, top_k),
        e, capacity, dtype)
    y_buf = _expert_ffn(x_buf, p.w_gate, p.w_up, p.w_down, dtype)
    out = _combine_local(y_buf, pos, flat_e, topw.reshape(t, top_k),
                         capacity, d, dtype).reshape(b, s, d)
    if p.shared_w_gate is not None:
        out = out + _shared_ffn(x, p, dtype)
    return out, aux


def _shared_ffn(x, p: MoEParams, dtype):
    gs = jnp.einsum("bsd,df->bsf", x, p.shared_w_gate.astype(dtype))
    us = jnp.einsum("bsd,df->bsf", x, p.shared_w_up.astype(dtype))
    return jnp.einsum("bsf,fd->bsd", jax.nn.silu(gs) * us,
                      p.shared_w_down.astype(dtype))


def _moe_shard_map(x, p: MoEParams, tope, topw, top_k, capacity_factor,
                   groups, batch_axes):
    """Expert-parallel interior with explicit all-to-alls (see module doc).

    Device-local token count t_l = T / (gd*gm); local capacity
    C = ceil(t_l*k*cf/E) rounded up to a multiple of gm so the all-to-all
    tiles evenly.  Wire bytes per device per direction = E*C*D — the routed
    token bytes, nothing else."""
    from jax.sharding import PartitionSpec as P

    b, s, d = x.shape
    e = p.router.shape[1]
    gd, gm = groups
    t = b * s
    tl = t // (gd * gm)
    dtype = x.dtype
    capacity = max(int(tl * top_k * capacity_factor / e), 2)
    capacity = -(-capacity // gm) * gm            # multiple of gm

    bax = batch_axes
    xspec = P(bax, "model", None)
    kspec = P(bax, "model", None)

    def interior(x_l, tope_l, topw_l, wg, wu, wd):
        bl, sl, _ = x_l.shape
        t_l = bl * sl
        x_buf, pos, flat_e = _dispatch_local(
            x_l.reshape(t_l, d), tope_l.reshape(t_l, top_k),
            topw_l.reshape(t_l, top_k), e, capacity, dtype)
        # (E, C, D) -> split E across model axis -> (E/gm, gm*C, D)
        x_recv = jax.lax.all_to_all(x_buf, "model", split_axis=0,
                                    concat_axis=1, tiled=True)
        y_recv = _expert_ffn(x_recv, wg, wu, wd, dtype)
        y_buf = jax.lax.all_to_all(y_recv, "model", split_axis=1,
                                   concat_axis=0, tiled=True)
        out = _combine_local(y_buf, pos, flat_e, topw_l.reshape(t_l, top_k),
                             capacity, d, dtype)
        return out.reshape(bl, sl, d)

    # mesh=None: the ambient mesh installed by the caller's jax.set_mesh
    fn = jax.shard_map(
        interior,
        in_specs=(xspec, kspec, kspec,
                  P("model", None, None), P("model", None, None),
                  P("model", None, None)),
        out_specs=xspec,
        check_vma=False,
    )
    out = fn(x, tope, topw, p.w_gate, p.w_up, p.w_down)
    out = _constrain(out, (bax, None, None))
    if p.shared_w_gate is not None:
        out = out + _shared_ffn(x, p, dtype)
    return out

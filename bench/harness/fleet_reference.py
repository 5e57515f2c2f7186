"""Plain reference of the multi-tenant fleet's semantics, for deciding
`correct` in a configuration run by the ``fleet`` harness.

It imports nothing of the program; from ``bench/reference.py`` (the
one-tenant reference) it takes the observe step, the per-lane migration
and the two-tier cost model.  It restates what one epoch of the six-lane
runtime means when tenants share one block space and one fast tier under
quotas:

* the block space: each tenant's configuration (``bench/configs/``) in the
  order the fleet lists them, tenant ``t``'s block ``b`` at global id
  ``offsets[t] + b``;
* the merged geometry (:func:`geometry`): the smallest PEBS period, the
  bytes per access weighted by each tenant's accesses per epoch, the block
  bytes weighted by each tenant's blocks, a scanner that unmaps
  ``n_blocks // batches_per_epoch`` pages before each batch, and each
  tenant's cap, the fast tier apportioned by the weights (largest
  remainder, ties to the lowest tenant);
* observe, batch by batch, as the one-tenant reference;
* decide, per lane: each tenant keeps the top ``caps[t]`` of the lane's
  key on its own slice (``lax.top_k``: ties to the lowest block id), every
  other block's key drops below all of them, and the lane takes the global
  top ``k_hot`` of what is left, gated per lane as the one-tenant
  reference gates;
* account, globally, under the placement that served the epoch, with the
  epoch's hot set the exact top ``k_hot`` of the ground truth; per tenant,
  with the tenant's hot set the top ``hot_k[t]`` of its own slice, its
  counts summed over its id range, and its times priced in float64 with
  its own bytes per access and block bytes; the lane's host tax is split
  by the tenant's share of the epoch's accesses.

``control=True`` computes every float32 quantity in bfloat16 instead, the
configuration's stated score precision one step down.  It must come out as
not correct.
"""
from __future__ import annotations

import json
from functools import partial
from pathlib import Path
from typing import Dict, List, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from reference import _COLLECTOR, _Cost, _apply_lane, _observe_batch

__all__ = ["geometry", "run_reference", "tenant_configs", "TENANT_FIELDS"]

CONFIGS = Path(__file__).resolve().parents[1] / "configs"
TENANT_FIELDS = ("epoch", "lane", "tenant", "time_s", "access_s",
                 "host_tax_s", "migration_s", "accuracy", "coverage",
                 "resident", "promoted", "demoted", "n_fast", "n_slow",
                 "hot_k")


def tenant_configs(config: dict) -> List[dict]:
    """Each tenant's configuration: its file under ``bench/configs/``, or
    the dict a shrunk fleet carries in its place."""
    out = []
    for t in config["tenants"]:
        c = t["config"]
        out.append(c if isinstance(c, dict)
                   else json.loads((CONFIGS / f"{c}.json").read_text()))
    return out


def _apportion(weights: List[float], total: int) -> List[int]:
    w = np.asarray(weights, np.float64)
    exact = w * (total / w.sum())
    caps = np.floor(exact).astype(np.int64)
    short = total - int(caps.sum())
    for t in sorted(range(len(w)), key=lambda t: -(exact[t] - caps[t]))[
            :short]:
        caps[t] += 1
    return [int(c) for c in caps]


def geometry(config: dict, traffic: dict) -> dict:
    """The fleet's block space and the numbers it derives from its
    tenants (module doc)."""
    confs = tenant_configs(config)
    names = [t["name"] for t in config["tenants"]]
    mixes = [traffic["tenants"][name] for name in names]
    sizes = [int(c["n_blocks"]) for c in confs]
    offsets = tuple(int(o) for o in np.concatenate([[0], np.cumsum(sizes)]))
    n = offsets[-1]
    accesses = np.asarray([int(m["batches_per_epoch"]) * int(m["batch"])
                           for m in mixes], np.float64)
    bpa = np.asarray([float(c["bytes_per_access"]) for c in confs])
    bb = np.asarray([float(c["block_bytes"]) for c in confs])
    k = int(config["k_hot"])
    return dict(
        names=names, offsets=offsets, n_blocks=n, k_hot=k,
        hot_k=tuple(min(int(c["k_hot"]), s) for c, s in zip(confs, sizes)),
        caps=tuple(_apportion([t["weight"] for t in config["tenants"]], k)),
        pebs_period=min(int(c["pebs_period"]) for c in confs),
        bytes_per_access=float(np.sum(bpa * accesses) / np.sum(accesses)),
        block_bytes=float(np.sum(bb * np.asarray(sizes, np.float64))
                          / float(n)),
        nb_scan_rate=max(n // int(traffic["batches_per_epoch"]), 1),
        tenant_bytes_per_access=bpa.tolist(),
        tenant_block_bytes=bb.tolist())


def _top_mask(x: jax.Array, m: int) -> jax.Array:
    """Membership in ``lax.top_k(x, m)``."""
    return jnp.zeros(x.shape, jnp.bool_).at[jax.lax.top_k(x, m)[1]].set(True)


@partial(jax.jit, donate_argnums=0,
         static_argnames=("lanes", "k", "offsets", "caps", "hot_k", "period",
                          "alpha", "w", "control"))
def _decide(s, thr, *, lanes, k, offsets, caps, hot_k, period, alpha, w,
            control):
    ft = jnp.bfloat16 if control else jnp.float32
    n = s["hmu"].shape[0]
    spans = list(zip(offsets, offsets[1:]))
    d_true = s["true"] - s["prev_true"]
    d_hmu = s["hmu"] - s["prev_hmu"]
    pebs_now = s["sampled"] * period
    d_pebs = pebs_now - s["prev_pebs"]
    nb = s["faults"]
    d_hmu_f = d_hmu.astype(ft)
    pred = alpha * d_hmu_f + (1.0 - alpha) * s["pred"]

    def topk(key):
        kept = jnp.concatenate([_top_mask(key[a:b], cap)
                                for (a, b), cap in zip(spans, caps)])
        low = (jnp.iinfo(key.dtype).min
               if jnp.issubdtype(key.dtype, jnp.integer) else -jnp.inf)
        return jax.lax.top_k(jnp.where(kept, key, low), k)

    hmu_sel = topk(d_hmu)
    sel_ids, ests, gates, reactive = [], [], [], []
    for name in lanes:
        if name in ("hmu_oracle", "reactive_watermark"):
            vals, ids = hmu_sel
            est = d_hmu_f
            gate = vals >= (thr if name == "reactive_watermark" else 1)
        elif name == "nb_two_touch":
            vals, ids = topk(nb)
            est, gate = nb.astype(ft), vals >= 2
        elif name == "proactive_ewma":
            vals, ids = topk(pred)
            est, gate = pred, vals > 0
        elif name == "hinted":
            # no hints in a fleet cell: the score is the telemetry rank
            t_rank = jnp.argsort(jnp.argsort(d_pebs, stable=True))
            t_rank = t_rank.astype(ft) if control else t_rank
            score = (1.0 - w) * (t_rank / max(n - 1, 1))
            score = jnp.where(d_pebs > 0, score, -1.0)
            vals, ids = topk(score)
            est, gate = d_pebs.astype(ft), vals >= 0
        elif name == "prefetch":
            # no lookahead: the rank is zero everywhere and nothing passes
            look = jnp.zeros((n,), ft)
            vals, ids = topk(look)
            est, gate = look, vals > 0
        else:
            raise ValueError(name)
        sel_ids.append(ids)
        gates.append(gate)
        ests.append(est.astype(jnp.float32) if control else est)
        reactive.append(name == "reactive_watermark")
    ids = jnp.stack(sel_ids)
    gate = jnp.stack(gates)
    est = jnp.stack(ests)
    reactive = jnp.asarray(reactive)

    # account the epoch under the placement that served it
    hot = _top_mask(d_true, k)
    t_hot = jnp.concatenate([_top_mask(d_true[a:b], h)
                             for (a, b), h in zip(spans, hot_k)])
    s2b, b2s = s["s2b"], s["b2s"]
    fast0 = b2s >= 0
    n_fast = jnp.sum(jnp.where(fast0, d_true[None, :], 0), axis=-1)
    n_slow = jnp.sum(d_true) - n_fast
    inter = jnp.sum((fast0 & hot[None, :]).astype(jnp.int32), axis=-1)
    resident = jnp.sum((s2b >= 0).astype(jnp.int32), axis=-1)

    # the watermark lane frees residents its epoch estimate calls idle
    idle = fast0 & (est == 0) & reactive[:, None]
    pre_demoted = jnp.sum(idle.astype(jnp.int32), axis=-1)
    b2s = jnp.where(idle, -1, b2s)
    s2b = jnp.where((s2b >= 0) & jnp.take_along_axis(
        idle, jnp.maximum(s2b, 0), axis=-1), -1, s2b)
    free = jnp.sum((s2b < 0).astype(jnp.int32), axis=-1)
    cap = jnp.where(reactive, jnp.minimum(k, free), k)
    ok = gate & (jnp.arange(k)[None, :] < cap[:, None])
    want = jnp.where(ok, ids, -1)
    s2b, b2s, promoted, demoted = jax.vmap(_apply_lane)(s2b, b2s, want, est)
    fast1 = b2s >= 0

    def per_tenant(x):                   # (L, n) -> (L, T) int32 sums
        return jnp.stack([jnp.sum(x[:, a:b].astype(jnp.int32), axis=-1)
                          for a, b in spans], axis=-1)

    pred_keep = pred if "proactive_ewma" in lanes else s["pred"]
    out = dict(s, s2b=s2b, b2s=b2s, pred=pred_keep, prev_true=s["true"],
               prev_hmu=s["hmu"], prev_pebs=pebs_now)
    rows = dict(n_fast=n_fast, n_slow=n_slow, inter=inter, resident=resident,
                promoted=promoted, demoted=demoted + pre_demoted)
    tenant = dict(
        n_fast=per_tenant(jnp.where(fast0, d_true[None, :], 0)),
        n_slow=per_tenant(jnp.where(fast0, 0, d_true[None, :])),
        inter=per_tenant(fast0 & t_hot[None, :]),
        resident=per_tenant(fast0),
        promoted=per_tenant(fast1 & ~fast0),
        demoted=per_tenant(fast0 & ~fast1))
    return out, rows, tenant


def run_reference(config: dict, traffic: dict, pool, n_epochs: int, *,
                  control: bool = False
                  ) -> Tuple[Dict[str, List[dict]],
                             Dict[str, Dict[str, List[dict]]], np.ndarray]:
    """Replay epochs ``0 .. n_epochs-1`` of ``pool`` (cyclic, as served).

    Returns ``({lane: [record dict per epoch]}, {tenant: {lane: [tenant
    row dict per epoch]}}, final slot_to_block (L, k))``."""
    g = geometry(config, traffic)
    n, k = g["n_blocks"], g["k_hot"]
    names, offsets, hot_k = g["names"], g["offsets"], g["hot_k"]
    lanes = tuple(config["lanes"])
    L = len(lanes)
    rt = config["runtime"]
    period = g["pebs_period"]
    scan_rate = g["nb_scan_rate"]
    log_cap = int(rt["hmu_log_capacity"])
    cost = _Cost(config["memory_system"])
    per_event = config["host_cost_per_event_s"]
    bpa, bb = g["bytes_per_access"], g["block_bytes"]
    overlap = float(rt["prefetch_overlap"])
    ft = jnp.bfloat16 if control else jnp.float32

    def zi():
        return jnp.zeros((n,), jnp.int32)

    s = dict(hmu=zi(), true=zi(), sampled=zi(),
             mapped=jnp.ones((n,), jnp.bool_), faults=zi(),
             prev_true=zi(), prev_hmu=zi(), prev_pebs=zi(),
             pred=jnp.zeros((n,), ft),
             s2b=jnp.full((L, k), -1, jnp.int32),
             b2s=jnp.full((L, n), -1, jnp.int32))
    pos = 0                # accesses observed so far (stream position)
    scan_ptr = 0
    log_used = 0
    pending = 0            # prefetch lane's migration issued last boundary
    records: Dict[str, List[dict]] = {name: [] for name in lanes}
    tenants: Dict[str, Dict[str, List[dict]]] = {
        t: {name: [] for name in lanes} for t in names}
    for e in range(n_epochs):
        batches = pool.epoch(e)
        n_sample_pad = batches.shape[1] // period + 1
        accesses = int(batches.size)
        pebs_events = 0
        nb_dev = []
        for b in range(batches.shape[0]):
            ids = batches[b]
            first = (-pos) % period
            sampled = ids[first::period]
            pebs_events += sampled.shape[0]
            padded = np.full((n_sample_pad,), n, np.int32)
            padded[:sampled.shape[0]] = sampled
            s, faulted = _observe_batch(s, jnp.asarray(ids),
                                        jnp.asarray(padded),
                                        jnp.int32(scan_ptr),
                                        scan_rate=scan_rate)
            nb_dev.append(faulted)
            scan_ptr = (scan_ptr + scan_rate) % n
            pos += ids.shape[0]
            log_used += min(ids.shape[0], max(log_cap - log_used, 0))
        drained, log_used = log_used, 0
        thr = max(2, accesses // (8 * max(k, 1)))
        s, rows, per_t = _decide(
            s, jnp.int32(thr), lanes=lanes, k=k, offsets=offsets,
            caps=g["caps"], hot_k=hot_k, period=period,
            alpha=float(rt["ewma_alpha"]), w=float(rt["hint_weight"]),
            control=control)
        rows, per_t = jax.device_get((rows, per_t))
        nb_events = int(sum(int(x) for x in jax.device_get(nb_dev)))
        for i, name in enumerate(lanes):
            col = _COLLECTOR[name]
            host_events = float({"hmu": drained, "nb": nb_events,
                                 "pebs": pebs_events, "prefetch": 0}[col])
            n_fast = float(rows["n_fast"][i])
            n_slow = float(rows["n_slow"][i])
            promoted = int(rows["promoted"][i])
            demoted = int(rows["demoted"][i])
            resident = int(rows["resident"][i])
            inter = int(rows["inter"][i])
            access_s = cost.access(n_fast, n_slow, bpa)
            host_tax_s = host_events * float(per_event[col])
            hidden_s = 0.0
            if name == "prefetch":
                moved, pending = pending, promoted + demoted
                migration_s = cost.migration(moved, bb)
                ts = cost.tier(n_slow, n_slow * bpa, cost.slow)
                hidden_s = overlap * min(ts, cost.migration(moved, bb))
            else:
                migration_s = cost.migration(promoted + demoted, bb)
            records[name].append(dict(
                epoch=e, lane=name,
                time_s=access_s + host_tax_s + migration_s - hidden_s,
                access_s=access_s, host_tax_s=host_tax_s,
                migration_s=migration_s,
                accuracy=(inter / resident) if resident else 0.0,
                coverage=(inter / k) if k else 0.0,
                resident=resident, promoted=promoted, demoted=demoted,
                host_events=host_events, hidden_s=hidden_s, quality=1.0))

            # the tenants' slices of the lane, each in its own geometry
            t_fast = [int(x) for x in per_t["n_fast"][i]]
            t_slow = [int(x) for x in per_t["n_slow"][i]]
            total = float(sum(t_fast) + sum(t_slow))
            for t, tname in enumerate(names):
                t_inter = int(per_t["inter"][i][t])
                t_res = int(per_t["resident"][i][t])
                t_pro = int(per_t["promoted"][i][t])
                t_dem = int(per_t["demoted"][i][t])
                t_access = cost.access(t_fast[t], t_slow[t],
                                       g["tenant_bytes_per_access"][t])
                t_mig = cost.migration(t_pro + t_dem,
                                       g["tenant_block_bytes"][t])
                share = (t_fast[t] + t_slow[t]) / total if total else 0.0
                t_tax = host_tax_s * share
                tenants[tname][name].append(dict(
                    epoch=e, lane=name, tenant=tname,
                    time_s=t_access + t_tax + t_mig, access_s=t_access,
                    host_tax_s=t_tax, migration_s=t_mig,
                    accuracy=(t_inter / t_res) if t_res else 0.0,
                    coverage=t_inter / hot_k[t], resident=t_res,
                    promoted=t_pro, demoted=t_dem, n_fast=t_fast[t],
                    n_slow=t_slow[t], hot_k=hot_k[t]))
    return records, tenants, np.asarray(s["s2b"])

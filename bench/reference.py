"""Plain reference of the tiering runtime's semantics, for deciding `correct`.

It imports nothing of the program.  It restates, as directly as it can, what
one epoch of the six-lane runtime means:

* observe, batch by batch in stream order: an exact saturating per-block
  counter (HMU) with a bounded log, every ``pebs_period``-th access sampled
  (PEBS), a cyclic NUMA-balancing scanner that unmaps
  ``n_blocks // batches_per_epoch`` pages before each batch and counts the
  first touch of an unmapped page as a hint fault (NB), and the exact
  ground-truth histogram;
* decide, per lane, the exact top-``k_hot`` blocks of the lane's key
  (``lax.top_k``: descending, ties to the lowest block id), gated per lane;
* migrate: free slots first, else demote the coldest residents by the lane's
  estimate (still-wanted residents never), new blocks into free slots in
  ascending slot order, in plan order;
* account, in float64 on the host, with the configuration's memory system
  and per-event host costs.

The hints are recomputed on the host from the same pool: the static table
prior, one epoch of lookahead and the cosine phase detector.

``control=True`` computes every float32 quantity (the EWMA predictor, the
hinted lane's blended score, the lookahead rank and the eviction estimates)
in bfloat16 instead: the configuration's stated score precision, one step
down.  It must come out as not correct.
"""
from __future__ import annotations

from functools import partial
from typing import Dict, List, Tuple

import numpy as np

import jax
import jax.numpy as jnp

__all__ = ["run_reference", "RECORD_FIELDS"]

RECORD_FIELDS = ("epoch", "lane", "time_s", "access_s", "host_tax_s",
                 "migration_s", "accuracy", "coverage", "resident",
                 "promoted", "demoted", "host_events", "hidden_s", "quality")

INT32_MAX = np.iinfo(np.int32).max
_FREE_HEAT = np.float32(INT32_MAX)     # free slots sort after every resident
_COLLECTOR = {"hmu_oracle": "hmu", "reactive_watermark": "hmu",
              "proactive_ewma": "hmu", "nb_two_touch": "nb",
              "hinted": "pebs", "prefetch": "prefetch"}


# ------------------------------------------------------------------ hints
class _Hints:
    """Static prior x phase-detector scale, and the lookahead rank."""

    def __init__(self, config: dict, traffic: dict, rank_to_page):
        h, t = config["hints"], traffic["hints"]
        n = int(config["n_blocks"])
        self.n = n
        self.depth = int(t["depth"])
        self.decay = float(h["lookahead_decay"])
        det = h["detector"] if t.get("detector", True) else None
        self.det = det
        rpp = int(config.get("rows_per_page", 1))
        alpha = float(config["popularity"]["alpha"])
        clip = max(n // int(h["static_clip_divisor"]), 1)
        base = np.arange(n, dtype=np.float64) * rpp
        page_w = np.zeros((n,), np.float64)
        for j in range(1, rpp + 1):
            page_w += (base + j) ** (-alpha)
        page_w[clip:] = 0.0
        self.static = np.zeros((n,), np.float32)
        self.static[np.asarray(rank_to_page)] = (page_w / page_w[0]).astype(
            np.float32)
        self.scale = 1.0
        self.ewma = None

    def _hist(self, batches: np.ndarray) -> np.ndarray:
        return np.bincount(batches.ravel(),
                           minlength=self.n)[:self.n].astype(np.float64)

    def ranks(self, batches, upcoming) -> Tuple[np.ndarray, np.ndarray]:
        if self.det is not None:
            h = self._hist(batches)
            if self.ewma is None:
                self.ewma = h
            else:
                denom = np.linalg.norm(self.ewma) * np.linalg.norm(h)
                sim = float(self.ewma @ h / denom) if denom > 0.0 else 1.0
                if sim < self.det["threshold"]:
                    self.scale *= self.det["penalty"]
                    self.ewma = h
                else:
                    a = self.det["alpha"]
                    self.ewma = a * h + (1.0 - a) * self.ewma
        hint = (self.static if self.scale == 1.0
                else self.static * np.float32(self.scale))
        counts = np.zeros((self.n,), np.float64)
        for d, batches_d in enumerate(upcoming[: self.depth]):
            counts += (self.decay ** d) * self._hist(batches_d)
        top = counts.max()
        look = (np.zeros((self.n,), np.float32) if top <= 0.0
                else (counts / top).astype(np.float32))
        return hint, look


# ---------------------------------------------------------- device parts
@partial(jax.jit, donate_argnums=0, static_argnames=("scan_rate",))
def _observe_batch(s, ids, sampled_ids, scan_ptr, *, scan_rate):
    n = s["hmu"].shape[0]
    added = s["hmu"].at[ids].add(1)
    hmu = jnp.where(added < s["hmu"], INT32_MAX, added)      # saturate
    true = s["true"].at[ids].add(1)
    sampled = s["sampled"].at[sampled_ids].add(1, mode="drop")
    scan = (scan_ptr + jnp.arange(scan_rate, dtype=jnp.int32)) % n
    mapped = s["mapped"].at[scan].set(False)
    touched = jnp.zeros((n,), jnp.bool_).at[ids].set(True)
    faulted = touched & ~mapped
    out = dict(s, hmu=hmu, true=true, sampled=sampled,
               mapped=mapped | touched,
               faults=s["faults"] + faulted.astype(jnp.int32))
    return out, jnp.sum(faulted.astype(jnp.int32))


def _apply_lane(s2b, b2s, want, est):
    """Promote ``want`` (plan order, -1 = none) into one lane's fast tier."""
    k, n = s2b.shape[0], b2s.shape[0]
    valid = want >= 0
    wanted = jnp.zeros((n,), jnp.bool_).at[jnp.where(valid, want, n)].set(
        True, mode="drop")
    new = valid & (b2s[jnp.maximum(want, 0)] < 0)
    n_new = jnp.sum(new.astype(jnp.int32))
    need = n_new - jnp.sum((s2b < 0).astype(jnp.int32))
    occ = s2b >= 0
    blk = jnp.maximum(s2b, 0)
    heat = jnp.where(occ, jnp.where(wanted[blk], jnp.inf,
                                    est[blk].astype(jnp.float32)),
                     _FREE_HEAT)
    order = jnp.argsort(heat, stable=True)             # coldest slot first
    rank = jnp.zeros((k,), jnp.int32).at[order].set(
        jnp.arange(k, dtype=jnp.int32))
    victim = occ & (rank < need)
    b2s = b2s.at[jnp.where(victim, s2b, n)].set(-1, mode="drop")
    s2b = jnp.where(victim, -1, s2b)
    free_slots = jnp.nonzero(s2b < 0, size=k, fill_value=k)[0]
    new_pos = jnp.nonzero(new, size=k, fill_value=k - 1)[0]
    n_free = jnp.sum((s2b < 0).astype(jnp.int32))
    assign = jnp.arange(k) < jnp.minimum(n_new, n_free)
    new_ids = want[new_pos]
    s2b = s2b.at[jnp.where(assign, free_slots, k)].set(new_ids, mode="drop")
    b2s = b2s.at[jnp.where(assign, new_ids, n)].set(
        free_slots.astype(jnp.int32), mode="drop")
    return (s2b, b2s, jnp.sum(assign.astype(jnp.int32)),
            jnp.sum(victim.astype(jnp.int32)))


@partial(jax.jit, donate_argnums=0,
         static_argnames=("lanes", "k", "period", "alpha", "w", "control"))
def _decide(s, hint_rank, prefetch_rank, thr, *, lanes, k, period, alpha, w,
            control):
    ft = jnp.bfloat16 if control else jnp.float32
    n = s["hmu"].shape[0]
    d_true = s["true"] - s["prev_true"]
    d_hmu = s["hmu"] - s["prev_hmu"]
    pebs_now = s["sampled"] * period
    d_pebs = pebs_now - s["prev_pebs"]
    nb = s["faults"]
    d_hmu_f = d_hmu.astype(ft)
    pred = alpha * d_hmu_f + (1.0 - alpha) * s["pred"]

    def topk(key):
        return jax.lax.top_k(key, k)

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
            # telemetry rank: position in a stable ascending sort; the
            # int32 rank over (n - 1) is a float32 quotient, as stated
            t_rank = jnp.argsort(jnp.argsort(d_pebs, stable=True))
            t_rank = t_rank.astype(ft) if control else t_rank
            score = ((1.0 - w) * (t_rank / max(n - 1, 1))
                     + w * hint_rank.astype(ft))
            score = jnp.where((d_pebs > 0) | (hint_rank > 0), score, -1.0)
            vals, ids = topk(score)
            est, gate = d_pebs.astype(ft), vals >= 0
        elif name == "prefetch":
            look = prefetch_rank.astype(ft)
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
    hot = jnp.zeros((n,), jnp.bool_).at[jax.lax.top_k(d_true, k)[1]].set(True)
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

    pred_keep = pred if "proactive_ewma" in lanes else s["pred"]
    out = dict(s, s2b=s2b, b2s=b2s, pred=pred_keep, prev_true=s["true"],
               prev_hmu=s["hmu"], prev_pebs=pebs_now)
    rows = dict(n_fast=n_fast, n_slow=n_slow, inter=inter, resident=resident,
                promoted=promoted, demoted=demoted + pre_demoted)
    return out, rows


# ------------------------------------------------------------- accounting
class _Cost:
    """The configuration's two-tier memory system, in float64."""

    def __init__(self, ms: dict):
        self.fast, self.slow, self.mlp = ms["fast"], ms["slow"], ms["mlp"]

    def tier(self, n_acc: float, nbytes: float, tier: dict) -> float:
        lat = n_acc * tier["latency_ns"] * 1e-9 / self.mlp
        bw = nbytes / (tier["bandwidth_gbps"] * 1e9)
        return max(lat, bw)

    def access(self, n_fast: float, n_slow: float, bpa: float) -> float:
        tf = self.tier(n_fast, n_fast * bpa, self.fast)
        ts = self.tier(n_slow, n_slow * bpa, self.slow)
        return tf + ts * (1.0 - 0.0)

    def migration(self, n_blocks: float, block_bytes: float) -> float:
        return self.tier(n_blocks, n_blocks * block_bytes, self.slow)


def run_reference(config: dict, traffic: dict, pool, n_epochs: int, *,
                  control: bool = False
                  ) -> Tuple[Dict[str, List[dict]], np.ndarray]:
    """Replay epochs ``0 .. n_epochs-1`` of ``pool`` (cyclic, as served).

    Returns ``({lane: [record dict per epoch]}, final slot_to_block (L, k))``.
    """
    n, k = int(config["n_blocks"]), int(config["k_hot"])
    lanes = tuple(config["lanes"])
    L = len(lanes)
    rt = config["runtime"]
    period = int(config["pebs_period"])
    bpe = int(traffic["batches_per_epoch"])
    scan_rate = max(n // (bpe * int(config["nb_scan_passes_per_epoch"])), 1)
    log_cap = int(rt["hmu_log_capacity"])
    cost = _Cost(config["memory_system"])
    per_event = config["host_cost_per_event_s"]
    bpa, bb = float(config["bytes_per_access"]), float(config["block_bytes"])
    overlap = float(rt["prefetch_overlap"])
    hints = (_Hints(config, traffic, pool.rank_to_page)
             if traffic.get("hints") else None)
    depth = hints.depth if hints is not None else 0
    zeros_f = np.zeros((n,), np.float32)
    ft = jnp.bfloat16 if control else jnp.float32

    def zi():
        return jnp.zeros((n,), jnp.int32)

    s = dict(hmu=zi(), true=zi(), sampled=zi(),
             mapped=jnp.ones((n,), jnp.bool_), faults=zi(),
             prev_true=zi(), prev_hmu=zi(), prev_pebs=zi(),
             pred=jnp.zeros((n,), ft),
             s2b=jnp.full((L, k), -1, jnp.int32),
             b2s=jnp.full((L, n), -1, jnp.int32))
    g = 0                  # accesses observed so far (stream position)
    scan_ptr = 0
    log_used = 0
    pending = 0            # prefetch lane's migration issued last boundary
    n_sample_pad = int(traffic["batch"]) // period + 1
    records: Dict[str, List[dict]] = {name: [] for name in lanes}
    for e in range(n_epochs):
        batches = pool.epoch(e)
        if hints is not None:
            hint_rank, look = hints.ranks(batches, pool.upcoming(e, depth))
        else:
            hint_rank, look = zeros_f, zeros_f
        accesses = int(batches.size)
        pebs_events = nb_events = 0
        nb_dev = []
        for b in range(batches.shape[0]):
            ids = batches[b]
            m = ids.shape[0]
            first = (-g) % period
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
            g += m
            log_used += min(m, max(log_cap - log_used, 0))
        drained, log_used = log_used, 0
        thr = max(2, accesses // (8 * max(k, 1)))
        s, rows = _decide(s, jnp.asarray(hint_rank), jnp.asarray(look),
                          jnp.int32(thr), lanes=lanes, k=k, period=period,
                          alpha=float(rt["ewma_alpha"]),
                          w=float(rt["hint_weight"]), control=control)
        rows = jax.device_get(rows)
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
    return records, np.asarray(s["s2b"])

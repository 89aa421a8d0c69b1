"""Static cost-certificate passes (ISSUE 10 tentpole).

Four passes built on :mod:`repro.analysis.cost_model` — the executable
restatement of the paper's Figures 10–11 claims, run abstractly (no
compilation, no FLOPs) against the REAL engine entry points:

* :class:`DispatchCostScaling` (``cost-dispatch-scaling``) — for every
  ``(backend, kv_buckets, mesh)`` dispatch group, trace
  ``dispatch_layer`` at three matched-capacity sequence lengths and
  certify the FLOP/byte totals are EXACTLY affine in ``T_kv`` (zero
  second difference — any smuggled dense ``T_kv``-wide einsum is
  super-linear and blows the curvature), with the linear per-token
  coefficient bounded by the dense K/V-projection budget the dispatch
  legitimately pays (traced from the same cost model, ×
  :data:`KAPPA_TOKEN` slack).  At fixed ``n`` three plan densities
  certify the live-slot slope: cost strictly increases with the plan's
  ``q``/pair slot capacities (GEMM-Q against live ``q`` slots, GEMM-O /
  attention against the pair-slot product).  Finally every registered
  strategy's dispatch trace must cost bit-identically to its group
  baseline — ``dispatch_layer`` never consults the strategy, so ANY
  cost difference means strategy content leaked into Dispatch.
* :class:`CollectiveBytesBudget` (``cost-collective-bytes``) — the mesh
  seq-mode dispatch's all-to-all payload must EQUAL the ``pair_cap``
  formula ``2 · B·H·P·pair_cap·block_kv·dh · itemsize`` (one exchange
  per K and V), stay under half the dense KV all-gather baseline at 25%
  density, and bring no other collective kind; head mode spends zero
  collectives.  This subsumes the HLO-text heuristic in
  ``launch/dryrun.collective_bytes`` (now a cross-checked consumer).
* :class:`UpdateAmortization` (``cost-update-amortization``) — Update
  (dense step + symbol emit + plan build) costs at most
  :data:`KAPPA_UPDATE` × one dense reference step, and the
  interval-amortized engine ``(update + (interval−1)·dispatch) /
  interval`` beats :data:`THETA_AMORTIZED` × dense — an engine that
  rebuilds the plan every dispatch pays update-cost every step and
  fails this line.
* :class:`MemoryFootprint` (``cost-memory-footprint``) — the peak-live
  -buffer estimate of every traced executable stays inside
  :data:`PEAK_BUDGETS` (measured on the seed geometry + headroom), and
  the serving lane-scan tick's peak is affine in the lane count: the
  marginal bytes of lanes 2→4 and 4→6 must agree, so a lane-count
  change can never alter per-lane bytes (a ``lanes²`` buffer fails).

All thresholds were calibrated against the engine at the analyzer's
tiny trace geometry and hold with 30–50% headroom; they are meant to
catch order-of-magnitude regressions (dense work on the dispatch path,
full-KV collectives, plan rebuilds per step), not 1% drift.

The ``*_findings`` helpers are pure functions over
:class:`~repro.analysis.cost_model.CostEstimate` values so the
adversarial CLI fixtures (``python -m repro.analysis --fixture
cost-*``) and tests can feed them poisoned traces directly.
"""

from __future__ import annotations

import dataclasses
import itertools
from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp

from repro.analysis.cost_model import (CostEstimate, cost_of_jaxpr,
                                       peak_bytes_of)
from repro.analysis.passes import (_B, _DH, _DM, _H, _N, _engine_cfg,
                                   _params, mesh_capacity, trace_pair)
from repro.core.lru import LruCache

__all__ = ["DispatchCostScaling", "CollectiveBytesBudget",
           "UpdateAmortization", "MemoryFootprint", "COST_PASSES",
           "token_scaling_findings", "collective_findings",
           "amortization_findings", "footprint_findings",
           "expected_a2a_payload", "KAPPA_TOKEN", "KAPPA_UPDATE",
           "THETA_AMORTIZED", "PEAK_BUDGETS"]


# Matched-capacity sequence lengths for the T_kv-independence scan.
_NS = (128, 256, 384)

# Per-token FLOP/byte slack over the dense-projection reference (the
# K/V projections + RMSNorm + reuse/bias buffers dispatch must pay per
# token).  Measured slopes across all 8 groups: 0.85×–1.21× the FLOP
# reference, 1.7×–3.6× the byte reference (mesh groups stage the local
# KV slice per shard).
KAPPA_TOKEN = 2.0
KAPPA_TOKEN_BYTES = 5.0

# Update ≤ KAPPA_UPDATE × dense step (measured 1.10× flops, 1.47×
# bytes); amortized interval ≤ THETA_AMORTIZED × dense (measured 0.62×
# xla / 0.72× pallas at 50% density; a rebuild-every-dispatch engine
# sits at the update ratio ≥ 1.09 and fails).
KAPPA_UPDATE = 1.5
KAPPA_UPDATE_BYTES = 2.5
THETA_AMORTIZED = 0.95

# Peak-live-byte budgets at the analyzer trace geometry (measured max
# across the 8 dispatch groups: update 380 KB, dispatch 530 KB; lane
# tick base 966 KB + 311 KB/lane).  ~35% headroom.
PEAK_BUDGETS = {
    "update_layer": 512_000,
    "dispatch_layer": 720_000,
    "lane_tick_base": 1_400_000,
    "lane_tick_per_lane": 450_000,
}
# Lane marginals must agree to this relative tolerance (measured 0.0).
LANE_MARGINAL_RTOL = 0.02

_COST_CACHE = LruCache(maxsize=256)


def dispatch_groups(kv_buckets=(1, 3), meshes=(False, True)):
    """The strategy-independent dispatch trace grid: ``dispatch_layer``
    never consults ``cfg.strategy``, so one (backend, kv_buckets, mesh)
    cell covers every strategy's dispatch jaxpr."""
    for backend, kvb, mesh in itertools.product(
            ("xla", "pallas"), kv_buckets, meshes):
        label = f"{backend}/kv_buckets={kvb}/{'mesh' if mesh else 'single'}"
        kw = dict(backend=backend, kv_buckets=kvb)
        if backend == "pallas":
            kw["interpret"] = True
        if mesh:
            if mesh_capacity() < 2:
                yield label, None, "needs >= 2 devices"
                continue
            kw.update(mesh_dp=1, mesh_sp=2)
        yield label, _engine_cfg(**kw), None


def _matched(cfg, capq_cmp: int, capkv_cmp: int, n: int):
    """Pin the COMPRESSED-granularity capacities regardless of ``n`` so
    the block caps (and hence the plan's live slots) stay constant while
    ``T_kv`` scales — the knob behind the T_kv-independence scan."""
    t = cfg.mask.n_blocks(n)
    return dataclasses.replace(cfg, cap_q_frac=capq_cmp / t,
                               cap_kv_frac=capkv_cmp / t)


def _dispatch_cost(cfg, n: int) -> CostEstimate:
    key = ("dispatch", cfg, n)
    hit = _COST_CACHE.get(key)
    if hit is not None:
        return hit
    _, disp = trace_pair(cfg, n=n, dispatch_only=True)
    return _COST_CACHE.put(key, cost_of_jaxpr(disp))


def _update_cost(cfg, n: int) -> CostEstimate:
    key = ("update", cfg, n)
    hit = _COST_CACHE.get(key)
    if hit is not None:
        return hit
    upd, _ = trace_pair(cfg, n=n)
    return _COST_CACHE.put(key, cost_of_jaxpr(upd))


def _dense_reference_cost(n: int) -> CostEstimate:
    """One dense attention step (projections + dense attention + output
    GEMM) — the UpdateAmortization yardstick."""
    from repro.core.attention import dense_attention
    from repro.core.engine import _project_heads, _qk
    p = _params()

    def dense_layer(x):
        q, k = _qk(p, x, _H, None)
        v = _project_heads(x, p.wv, _H)
        o = dense_attention(q, k, v)
        wo_h = p.wo.reshape(_H, _DH, _DM)
        return jnp.einsum("bnhd,hdf->bnf", o.transpose(0, 2, 1, 3), wo_h)

    key = ("dense-ref", n)
    hit = _COST_CACHE.get(key)
    if hit is not None:
        return hit
    jx = jax.make_jaxpr(dense_layer)(
        jax.ShapeDtypeStruct((_B, n, _DM), jnp.float32))
    return _COST_CACHE.put(key, cost_of_jaxpr(jx))


def _token_reference_slope() -> tuple:
    """(flops, bytes) per token of the work dispatch legitimately pays
    for EVERY token regardless of the plan: dense K/V projections,
    RMSNorm, and the reuse/bias buffers.  Traced from the cost model
    itself so the budget tracks the engine, not a hand-typed constant."""
    from repro.core.engine import _project_heads, rms_norm
    p = _params()

    def per_token(x):
        k_h = rms_norm(_project_heads(x, p.wk, _H), p.k_scale)
        v_h = _project_heads(x, p.wv, _H)
        o_reuse = jnp.zeros((x.shape[0], _H, x.shape[1], _DH), x.dtype)
        return k_h, v_h, o_reuse, x + jnp.zeros_like(x)

    key = ("token-ref",)
    hit = _COST_CACHE.get(key)
    if hit is not None:
        return hit
    costs = [cost_of_jaxpr(jax.make_jaxpr(per_token)(
        jax.ShapeDtypeStruct((_B, n, _DM), jnp.float32)))
        for n in (_NS[0], _NS[1])]
    dn = _NS[1] - _NS[0]
    return _COST_CACHE.put(key, ((costs[1].flops - costs[0].flops) / dn,
                                 (costs[1].hbm_bytes - costs[0].hbm_bytes)
                                 / dn))


# ---------------------------------------------------------------------------
# Pure finding helpers (shared with the CLI fixtures / tests)
# ---------------------------------------------------------------------------

def token_scaling_findings(pass_name: str, where: str,
                           costs: Sequence[CostEstimate],
                           ns: Sequence[int],
                           budget_flops: float,
                           budget_bytes: float) -> List:
    """Certify ``costs`` over matched-capacity lengths ``ns``: exactly
    affine in n (zero curvature) with slope within the per-token budget."""
    from repro.analysis import Finding
    findings = []
    assert len(costs) == len(ns) == 3 and ns[2] - ns[1] == ns[1] - ns[0]
    dn = ns[1] - ns[0]
    for attr, budget, unit in (("flops", budget_flops, "flops"),
                               ("hbm_bytes", budget_bytes, "bytes")):
        v = [getattr(c, attr) for c in costs]
        d1, d2 = v[1] - v[0], v[2] - v[1]
        curv = abs(d2 - d1) / max(v[1], 1.0)
        if curv > 1e-9:
            findings.append(Finding(
                pass_name, "tkv-superlinear", where,
                f"{unit} not affine in T_kv at fixed plan capacity: "
                f"Δ({ns[0]}->{ns[1]})={d1:.0f} vs Δ({ns[1]}->{ns[2]})="
                f"{d2:.0f} — dense T_kv-dependent work on the dispatch "
                f"path"))
        slope = d1 / dn
        if slope > budget:
            findings.append(Finding(
                pass_name, "token-slope-budget", where,
                f"per-token {unit} slope {slope:.0f} exceeds the dense-"
                f"projection budget {budget:.0f} — dispatch pays more "
                f"than the legitimate per-token work"))
    return findings


def expected_a2a_payload(cfg, n: int) -> float:
    """The pair_cap formula: 2 exchanges (K and V) of
    ``(B/dp, H, P, pair_cap, block_kv, dh)`` f32 blocks."""
    from repro.distributed.plan_shard import shard_geometry
    m = cfg.mask
    spec = cfg.caps(n)
    t_kv = m.n_blocks(n) * (m.pool // m.block_kv)
    geom = shard_geometry(spec, t_kv, t_kv, cfg.mesh_sp,
                          cfg.mesh_pair_slack)
    b_local = max(1, _B // cfg.mesh_dp)
    return 2.0 * (b_local * _H * cfg.mesh_sp * geom.pair_cap
                  * m.block_kv * _DH) * 4


def collective_findings(pass_name: str, where: str, cost: CostEstimate,
                        expected_payload: float,
                        dense_payload: float) -> List:
    """Certify a seq-mode mesh dispatch cost: exactly two all-to-alls
    whose payload equals the ``pair_cap`` formula, under half the dense
    all-gather, and nothing else on the wire."""
    from repro.analysis import Finding
    findings = []
    a2a = cost.coll_payload.get("all_to_all", 0.0)
    if cost.coll_count.get("all_to_all", 0) != 2:
        findings.append(Finding(
            pass_name, "a2a-count", where,
            f"expected exactly 2 all_to_all (one per K and V), found "
            f"{cost.coll_count.get('all_to_all', 0)}"))
    if a2a != expected_payload:
        findings.append(Finding(
            pass_name, "pair-cap-formula", where,
            f"all_to_all payload {a2a:.0f}B != pair_cap formula "
            f"{expected_payload:.0f}B — the exchange is not shipping "
            f"exactly the plan-live KV blocks"))
    extra = {k: v for k, v in cost.coll_payload.items()
             if k != "all_to_all" and v}
    if extra:
        findings.append(Finding(
            pass_name, "no-extra-collectives", where,
            f"unexpected collective bytes {extra} — mesh dispatch must "
            f"ship only the plan-aware a2a payload"))
    if dense_payload and a2a >= 0.5 * dense_payload:
        findings.append(Finding(
            pass_name, "dense-ratio", where,
            f"plan-aware payload {a2a:.0f}B >= 0.5x the dense KV "
            f"all-gather {dense_payload:.0f}B — O(T_kv) communication"))
    return findings


def amortization_findings(pass_name: str, where: str,
                          update_cost: CostEstimate,
                          dispatch_cost: CostEstimate,
                          dense_cost: CostEstimate,
                          interval: int) -> List:
    from repro.analysis import Finding
    findings = []
    if update_cost.flops > KAPPA_UPDATE * dense_cost.flops:
        findings.append(Finding(
            pass_name, "update-cost-bound", where,
            f"Update flops {update_cost.flops:.0f} > {KAPPA_UPDATE}x one "
            f"dense step ({dense_cost.flops:.0f}) — plan construction "
            f"dominates the interval"))
    if update_cost.hbm_bytes > KAPPA_UPDATE_BYTES * dense_cost.hbm_bytes:
        findings.append(Finding(
            pass_name, "update-bytes-bound", where,
            f"Update bytes {update_cost.hbm_bytes:.0f} > "
            f"{KAPPA_UPDATE_BYTES}x one dense step "
            f"({dense_cost.hbm_bytes:.0f})"))
    amort = (update_cost.flops + (interval - 1) * dispatch_cost.flops) \
        / (interval * dense_cost.flops)
    if amort > THETA_AMORTIZED:
        findings.append(Finding(
            pass_name, "interval-amortization", where,
            f"amortized interval cost {amort:.3f}x dense exceeds "
            f"{THETA_AMORTIZED}x — the Update is not amortized over the "
            f"interval (a plan rebuilt every dispatch lands here)"))
    return findings


def footprint_findings(pass_name: str, where: str, peak: float,
                       budget: float) -> List:
    from repro.analysis import Finding
    if peak <= budget:
        return []
    return [Finding(
        pass_name, "peak-bytes-budget", where,
        f"estimated peak live bytes {peak:.0f} exceed the declared "
        f"budget {budget:.0f} — a new executable-sized buffer joined "
        f"this trace")]


# ---------------------------------------------------------------------------
# The passes
# ---------------------------------------------------------------------------

class DispatchCostScaling:
    """Dispatch cost ∝ plan slots, never T_kv (the Fig. 10/11 claim)."""

    name = "cost-dispatch-scaling"

    def run(self, ctx) -> List:
        from repro.analysis import Finding
        from repro.core.strategy import available_strategies
        findings = []
        ref_f, ref_b = _token_reference_slope()
        for label, cfg0, skip in dispatch_groups():
            if skip is not None:
                ctx.note(f"{self.name}: skipped {label} ({skip})")
                continue
            # 1. T_kv-independence: matched caps, three lengths.
            costs = [_dispatch_cost(_matched(cfg0, 2, 2, n), n) for n in _NS]
            findings += token_scaling_findings(
                self.name, f"dispatch_layer[{label}]", costs, _NS,
                budget_flops=KAPPA_TOKEN * ref_f,
                budget_bytes=KAPPA_TOKEN_BYTES * ref_b)
            # 2. Live-slot slope: density scan at fixed n.
            n0 = _NS[0]
            dens = [(1, 1), (2, 2), (3, 4)]
            dcosts = [_dispatch_cost(_matched(cfg0, cq, ck, n0), n0)
                      for cq, ck in dens]
            slots = [cq * ck for cq, ck in dens]
            for i in range(1, len(dcosts)):
                if dcosts[i].flops <= dcosts[i - 1].flops:
                    findings.append(Finding(
                        self.name, "slot-slope", f"dispatch_layer[{label}]",
                        f"dispatch flops not increasing with live plan "
                        f"slots ({slots[i - 1]}->{slots[i]}): "
                        f"{dcosts[i - 1].flops:.0f} -> "
                        f"{dcosts[i].flops:.0f} — cost is not plan-"
                        f"proportional"))
            slope = (dcosts[-1].flops - dcosts[0].flops) / \
                (slots[-1] - slots[0])
            ctx.note(f"{self.name}: {label} slot slope "
                     f"{slope:.0f} flops/pair-slot, token slope "
                     f"{(costs[1].flops - costs[0].flops) / (_NS[1] - _NS[0]):.0f} "
                     f"flops/token (budget {KAPPA_TOKEN * ref_f:.0f})")
        # 3. Strategy leak: every strategy must cost its group baseline.
        base = {}
        for label, cfg0, skip in dispatch_groups():
            if skip is None:
                base[label] = _dispatch_cost(cfg0, _N)
        for strat in available_strategies():
            for label, cfg0, skip in dispatch_groups():
                if skip is not None:
                    continue
                cfg = dataclasses.replace(cfg0, strategy=strat)
                c = _dispatch_cost(cfg, _N)
                b = base[label]
                if (c.flops, c.hbm_bytes) != (b.flops, b.hbm_bytes) or \
                        c.coll_payload != b.coll_payload:
                    findings.append(Finding(
                        self.name, "strategy-leak",
                        f"dispatch_layer[{strat}/{label}]",
                        f"dispatch cost ({c.flops:.0f} flops, "
                        f"{c.hbm_bytes:.0f}B) differs from the group "
                        f"baseline ({b.flops:.0f}, {b.hbm_bytes:.0f}B) — "
                        f"strategy content reached the Dispatch jaxpr"))
        return findings


class CollectiveBytesBudget:
    """Mesh a2a bytes ≡ the pair_cap formula, never O(T_kv)."""

    name = "cost-collective-bytes"
    DENSITY_CMP = 2            # compressed-cap target ≈ 25% at n=256
    N = 256

    def run(self, ctx) -> List:
        from repro.analysis import Finding
        findings = []
        if mesh_capacity() < 2:
            ctx.note(f"{self.name}: skipped (needs >= 2 devices; run via "
                     "`make analyze` / python -m repro.analysis)")
            return findings
        cfg = _matched(_engine_cfg(backend="xla", mesh_dp=1, mesh_sp=2),
                       self.DENSITY_CMP, self.DENSITY_CMP, self.N)
        cost = _dispatch_cost(cfg, self.N)
        expected = expected_a2a_payload(cfg, self.N)
        # dense baseline: all-gather of the full K and V (result bytes
        # per shard — same convention as the dry-run HLO parser).
        dense_payload = 2.0 * (_B * _H * self.N * _DH) * 4
        findings += collective_findings(
            self.name, f"dispatch_layer[mesh seq, n={self.N}, "
            f"cap_cmp={self.DENSITY_CMP}]", cost, expected, dense_payload)
        ctx.note(f"{self.name}: a2a payload {cost.coll_payload.get('all_to_all', 0):.0f}B "
                 f"= pair_cap formula, {cost.coll_payload.get('all_to_all', 0) / dense_payload:.3f}x "
                 f"dense all-gather")
        # head mode: zero collectives of any kind.
        cfg_h = _engine_cfg(backend="xla", mesh_dp=1, mesh_sp=2,
                            mesh_axis="head")
        cost_h = _dispatch_cost(cfg_h, _N)
        if cost_h.coll_payload:
            findings.append(Finding(
                self.name, "head-mode-collectives",
                "dispatch_layer[mesh head]",
                f"head-mode dispatch spends collectives "
                f"{cost_h.coll_payload} — it must spend none"))
        return findings


class UpdateAmortization:
    """Update ≤ κ × dense; interval amortization beats θ × dense."""

    name = "cost-update-amortization"

    def run(self, ctx) -> List:
        findings = []
        dense = _dense_reference_cost(_N)
        for backend in ("xla", "pallas"):
            kw = dict(backend=backend, kv_buckets=1)
            if backend == "pallas":
                kw["interpret"] = True
            cfg = _matched(_engine_cfg(**kw), 2, 2, _N)   # 50% density
            u = _update_cost(cfg, _N)
            d = _dispatch_cost(cfg, _N)
            interval = cfg.mask.interval
            findings += amortization_findings(
                self.name, f"update/dispatch[{backend}]", u, d, dense,
                interval)
            ctx.note(f"{self.name}: {backend} update {u.flops / dense.flops:.2f}x "
                     f"dense, dispatch {d.flops / dense.flops:.2f}x, "
                     f"amortized {(u.flops + (interval - 1) * d.flops) / (interval * dense.flops):.2f}x, "
                     f"update bytes {u.hbm_bytes / dense.hbm_bytes:.2f}x")
        return findings


class MemoryFootprint:
    """Peak live bytes per executable within the declared budget table."""

    name = "cost-memory-footprint"
    LANES = (2, 4, 6)

    def run(self, ctx) -> List:
        from repro.analysis import Finding
        findings = []
        for label, cfg, skip in dispatch_groups():
            if skip is not None:
                ctx.note(f"{self.name}: skipped {label} ({skip})")
                continue
            upd, disp = trace_pair(cfg, n=_N)
            findings += footprint_findings(
                self.name, f"update_layer[{label}]", peak_bytes_of(upd),
                PEAK_BUDGETS["update_layer"])
            findings += footprint_findings(
                self.name, f"dispatch_layer[{label}]", peak_bytes_of(disp),
                PEAK_BUDGETS["dispatch_layer"])
        # Serving lane-scan tick: peak affine in lane count.
        peaks = self._tick_peaks(ctx)
        if peaks is not None:
            l0, l1, l2 = self.LANES
            m1 = (peaks[l1] - peaks[l0]) / (l1 - l0)
            m2 = (peaks[l2] - peaks[l1]) / (l2 - l1)
            if abs(m2 - m1) > LANE_MARGINAL_RTOL * max(m1, 1.0):
                findings.append(Finding(
                    self.name, "lane-bytes-affinity", "lane tick[scan]",
                    f"per-lane marginal peak bytes changed with the lane "
                    f"count: {m1:.0f}B/lane (lanes {l0}->{l1}) vs "
                    f"{m2:.0f}B/lane (lanes {l1}->{l2}) — a buffer "
                    f"scales super-linearly in lanes"))
            budget = PEAK_BUDGETS["lane_tick_base"] + \
                PEAK_BUDGETS["lane_tick_per_lane"] * max(self.LANES)
            findings += footprint_findings(
                self.name, f"lane tick[scan, lanes={max(self.LANES)}]",
                peaks[max(self.LANES)], budget)
            ctx.note(f"{self.name}: lane tick peak "
                     f"{peaks[max(self.LANES)] / 1e6:.2f}MB at "
                     f"{max(self.LANES)} lanes, marginal {m1:.0f}B/lane")
        return findings

    def _tick_peaks(self, ctx) -> Optional[dict]:
        from repro.analysis.passes import _serving_setup, _tick_avals
        from repro.diffusion.pipeline import make_lane_tick
        cfg, ecfg, scfg, strategies = _serving_setup()
        tick = make_lane_tick(cfg, ecfg, scfg, strategies)
        peaks = {}
        for lanes in self.LANES:
            av = _tick_avals(cfg, ecfg, scfg, lanes=lanes)
            try:
                jx = jax.make_jaxpr(tick)(
                    av["params"], av["patch_embed"], av["x"], av["states"],
                    av["text_emb"], av["step"], av["mode_tab"],
                    av["id_tab"], av["dt"], av["nsteps"], av["active"],
                    av["reset"])
            except Exception as e:      # noqa: BLE001 — reported as note;
                # the trace failure itself is ExecutableBudget's finding.
                ctx.note(f"{self.name}: lane tick trace failed ({e!r})")
                return None
            peaks[lanes] = peak_bytes_of(jx)
        return peaks


COST_PASSES = (DispatchCostScaling, CollectiveBytesBudget,
               UpdateAmortization, MemoryFootprint)

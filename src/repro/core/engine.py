"""FlashOmni Update–Dispatch engine (paper §3.2, Fig. 4).

The engine owns, per attention layer, the packed sparse symbols, the
TaylorSeer cache state and the GEMM-O cache bias, and exposes two step
functions over a generic attention module:

  * :func:`update_layer`   — full attention; refresh ``S_c``/``S_s`` from the
    fresh Q/K (mask generation of §3.3), refresh the TaylorSeer derivative
    stack and the GEMM-O bias ``B_c`` (stage 1 of §3.5).
  * :func:`dispatch_layer` — sparse execution guided by the frozen symbols:
    GEMM-Q skips cached row blocks, attention runs the structural sparse
    path (or the Pallas kernel on TPU), GEMM-O projects live heads and adds
    the Taylor-forecast bias.

Two cache modes (DESIGN §2.3/§2.4):
  * ``"bias"``    — paper-optimized: cache B_c in output space; cached
    blocks never touch the attention kernel (Eq. 4 makes this exact).
  * ``"o_cache"`` — paper-base: cache per-head attention outputs Õ and let
    the attention kernel's cache-then-reuse branch fill them.

Symbols are stored at the *compressed* granularity (pool = n·b) exactly as
in the paper (decode ``F(S_c, i) = (S_c >> i/n) & 1``), and expanded to
kernel-block granularity on use.

Update→plan→Dispatch dataflow (compile-once DispatchPlan):

    update_layer ──► strategy.emit(q, k, ctx) ──► SymbolSet (S_c, S_s,
                         │                         masks, clamp scores)
                         └─► build_dispatch_plan ──► DispatchPlan
                               (ALL unpack / expand / top-k / argsort
                                index work happens HERE, once per 𝒩 steps)
                         LayerState = (S_c, S_s, taylor, k_since, plan)

The symbol producer is pluggable (``EngineConfig.strategy`` — a
:mod:`repro.core.strategy` registry name, resolved once at trace time):
the paper's §3.3 rule is the ``"flashomni"`` strategy; ``"cache-all"``
(FORA/TaylorSeer), ``"skip-only"`` (SpargeAttn), ``"sliding-window"``
(DiTFastAttnV2), ``"multi-granularity"`` tables and ``"step-phased"``
(per-step re-classification) ride the same engine and kernels unchanged.
:func:`refresh_symbols` keeps the seed §3.3 body verbatim as the
bit-parity oracle for the ``flashomni`` strategy.  Whole (step × layer)
deployment plans are TRACED data: :func:`resolve_schedule` canonicalizes
the config into a :class:`~repro.core.schedule.SparsitySchedule`, and
``update_layer`` accepts a traced ``strategy_id`` over a schedule's
static strategy set (``strategy.emit_switch``) plus traced
``layer_idx``/``step_idx`` context.

    dispatch_layer ──► get_backend(cfg) ──► backend.{gemm_q, attention,
                                                      gemm_o}(…, plan)
                       consumes ``state.plan`` VERBATIM — a Dispatch jaxpr
                       contains no ``unpack_bits``/``clamp_mask_topk``/
                       ``active_indices`` work (see tests/test_backend.py).

Backend routing (``EngineConfig.backend``): ``"xla"`` structural path,
``"pallas"`` CSR kernels with compact GEMM-Q→attention layout fusion, or
``"auto"`` (Pallas on TPU hardware, XLA elsewhere).  The packed symbols
stay in the state as the canonical compressed representation (diagnostics,
resharding, and the paper's symbol-decode fidelity kernels).
"""

from __future__ import annotations

import dataclasses
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core import masks as masklib
from repro.core import sparse_gemm, taylorseer
from repro.core.lru import LruCache
from repro.core.attention import SparseAttentionSpec, dense_attention
from repro.core.backend import get_backend
from repro.core.masks import MaskConfig
from repro.core.plan import DispatchPlan, build_dispatch_plan, empty_plan_like
from repro.core.strategy import (SparsityStrategy, StrategyContext,
                                 emit_switch, get_strategy)
from repro.core.symbols import (
    capacity_for,
    clamp_mask_topk,
    pack_bits,
    packed_len,
    unpack_bits,
)

__all__ = [
    "EngineConfig",
    "LayerState",
    "AttnParams",
    "DispatchPlan",
    "init_layer_state",
    "is_update_step",
    "resolve_schedule",
    "schedule_cache_stats",
    "stack_lane_states",
    "gather_lane_states",
    "scatter_lane_states",
    "merge_lane_states",
    "set_lane_state",
    "update_layer",
    "dispatch_layer",
    "plan_from_state",
    "refresh_symbols",
    "rms_norm",
    "apply_rope",
]


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """Engine configuration = paper tuple (τ_q, τ_kv, 𝒩, 𝒟, S_q) + statics."""

    mask: MaskConfig = MaskConfig()
    cache_mode: str = "bias"          # "bias" | "o_cache"
    cap_q_frac: float = 0.75          # static live-Q capacity fraction
    cap_kv_frac: float = 0.9          # static KV-union capacity fraction
    use_gemm_q: bool = True
    use_gemm_o: bool = True
    cache_dtype: jnp.dtype = jnp.bfloat16
    backend: str = "xla"              # "xla" | "pallas" | "auto"
    interpret: Optional[bool] = None  # Pallas interpret mode (None: off-TPU)
    kv_buckets: int = 1               # occupancy buckets in the CSR grid
                                      # (1 = uniform cap_kv reduction;
                                      # 0 = AUTO: pick from the calibrated
                                      # occupancy histogram at schedule-
                                      # resolution time, see
                                      # kernels.tuning.select_kv_buckets;
                                      # see core.plan.bucket_geometry)
    strategy: str = "flashomni"       # sparse-symbol producer (registry name)
    schedule: Optional[str] = None    # named SparsitySchedule preset (overrides
                                      # the strategy/interval mapping in
                                      # resolve_schedule; see core.schedule)
    # Plan-sharded mesh dispatch (distributed/plan_shard.py).  mesh_sp > 1
    # routes attention through a shard_map over the (data, seq) engine
    # mesh; with mesh_axis == "seq" the plan carries per-shard partitions
    # + the plan-aware collective schedule (shd_* fields).  All statics —
    # they key jit caches and the LRU memos like every other field here.
    mesh_dp: int = 1                  # data-parallel shards (batch axis)
    mesh_sp: int = 1                  # sequence/head-parallel shards
    mesh_axis: str = "seq"            # "seq" (token shards + plan-aware
                                      # collectives) | "head" (no collectives)
    mesh_pair_slack: float = 1.5      # per-(src,dst) shipped-block capacity
                                      # slack over cap_kv/P (≥ 1 keeps the
                                      # per-shard union clamp a no-op)
    validate_plans: bool = False      # debug: run the structural plan
                                      # validator (analysis/plan_check.py)
                                      # on host after every plan build;
                                      # REPRO_VALIDATE_PLANS=1 turns it on
                                      # globally without touching configs

    # Capacity bookkeeping.  The single source of truth is the COMPRESSED
    # granularity capacity (symbols live there); block-granularity caps are
    # exact multiples so no live block can overflow the attention gather.
    def cap_q_cmp(self, n_tokens: int) -> int:
        return capacity_for(self.mask.n_blocks(n_tokens), self.cap_q_frac, quantum=1)

    def cap_kv_cmp(self, n_kv: int) -> int:
        return capacity_for(self.mask.n_blocks(n_kv), self.cap_kv_frac, quantum=1)

    def resolved_kv_buckets(self) -> int:
        """``kv_buckets`` with the 0 = "auto" sentinel resolved.

        Auto consults the calibration table's occupancy histogram for
        ``self.strategy`` (:func:`repro.kernels.tuning.select_kv_buckets`)
        — a pure function of the STATIC config, evaluated at schedule /
        spec-resolution time, so every jit cache keyed on this config
        still maps one configuration to one executable and Dispatch
        jaxprs stay sort-free.  Under a mesh the choice is forced to 1:
        the seq-sharded inner spec runs uniform per shard and the head
        mesh rejects buckets outright (distributed/plan_shard.py)."""
        if self.kv_buckets != 0:
            return self.kv_buckets
        if self.mesh_sp > 1:
            return 1
        from repro.kernels.tuning import select_kv_buckets
        return select_kv_buckets(self.strategy)

    def caps(self, n_tokens: int, n_kv: Optional[int] = None) -> SparseAttentionSpec:
        n_kv = n_tokens if n_kv is None else n_kv
        m = self.mask
        t_q = -(-n_tokens // m.block_q)
        t_kv = -(-n_kv // m.block_kv)
        fq, fk = m.pool // m.block_q, m.pool // m.block_kv
        return SparseAttentionSpec(
            block_q=m.block_q,
            block_kv=m.block_kv,
            cap_q=min(self.cap_q_cmp(n_tokens) * fq, t_q),
            cap_kv=min(self.cap_kv_cmp(n_kv) * fk, t_kv),
            kv_buckets=self.resolved_kv_buckets(),
        )


class AttnParams(NamedTuple):
    """Weights of one attention module (MMDiT joint-attention style)."""

    wq: jax.Array            # (dm, H*dh)
    wk: jax.Array
    wv: jax.Array
    wo: jax.Array            # (H*dh, dm)
    q_scale: jax.Array       # (dh,) RMSNorm scales (token-local, Obs. 2)
    k_scale: jax.Array


class LayerState(NamedTuple):
    """Per-layer engine state carried across denoising steps (a pytree)."""

    s_c: jax.Array                 # (B, H, cmp_bytes) uint8 — caching symbol
    s_s: jax.Array                 # (B, H, flat_bytes) uint8 — skipping symbol
    taylor: taylorseer.TaylorState  # over B_c (bias mode) or Õ (o_cache mode)
    k_since: jax.Array             # int32 — dispatch offset since last Update
    plan: DispatchPlan             # compile-once index plan (refreshed at Update)


def init_layer_state(
    batch: int, heads: int, n_tokens: int, d_model: int, head_dim: int, cfg: EngineConfig
) -> LayerState:
    t = cfg.mask.n_blocks(n_tokens)
    cbytes = packed_len(t)
    fbytes = packed_len(t * t)
    if cfg.cache_mode == "bias":
        feat = (batch, n_tokens, d_model)
    else:
        feat = (batch, heads, n_tokens, head_dim)
    return LayerState(
        s_c=jnp.full((batch, heads, cbytes), 255, jnp.uint8),
        s_s=jnp.full((batch, heads, fbytes), 255, jnp.uint8),
        taylor=taylorseer.init_state(feat, cfg.mask.order, cfg.cache_dtype),
        k_since=jnp.zeros((), jnp.int32),
        plan=empty_plan_like(batch, heads, n_tokens, cfg),
    )


def stack_lane_states(states: "LayerState", n_lanes: int) -> "LayerState":
    """Broadcast one request's engine state to ``n_lanes`` microbatch lanes.

    ``states`` is any LayerState pytree (typically the (L, ...)-stacked
    tree from ``models.dit.init_engine_states``); every leaf gains a
    leading ``(n_lanes, ...)`` lane axis.  The continuous batcher carries
    ONE such stacked tree and scans its lane axis per serving tick."""
    return jax.tree.map(
        lambda x: jnp.broadcast_to(x, (n_lanes, *x.shape)), states)


def gather_lane_states(stacked, lane_ids):
    """Gather lanes ``lane_ids`` of a lane-stacked pytree (device-side).

    ``lane_ids`` is any int array (host list or traced); every leaf is
    indexed along its leading lane axis — the general device-side lane
    SELECT the batched serving tick builds on (``jnp.take`` along axis 0,
    so the ids may themselves be traced data inside a jitted tick)."""
    ids = jnp.asarray(lane_ids, jnp.int32)
    return jax.tree.map(lambda s: jnp.take(s, ids, axis=0), stacked)


def scatter_lane_states(stacked, lane_ids, values):
    """Scatter ``values`` into lanes ``lane_ids`` of a lane-stacked pytree.

    ``values`` carries a leading axis of ``len(lane_ids)``; untouched lanes
    keep their state.  ``lane_ids`` must be unique (XLA scatter order is
    otherwise unspecified) and may be TRACED — this is the device-side
    generalization of :func:`set_lane_state` for use INSIDE compiled tick
    bodies, where the scatter lowers once per executable.  On the eager
    host path prefer :func:`set_lane_state`: a static-index update-slice
    dispatches several times faster than an array-index scatter."""
    ids = jnp.asarray(lane_ids, jnp.int32)
    return jax.tree.map(lambda s, v: s.at[ids].set(v.astype(s.dtype)),
                        stacked, values)


def merge_lane_states(old, new, lane_mask):
    """Per-lane select between two lane-stacked pytrees (device-side).

    ``lane_mask`` is a ``(lanes,)`` bool; True lanes take ``new``, False
    lanes keep ``old``.  Used by the batched mode-group tick bodies to
    write back ONLY the lanes that belong to the launched group — the
    fixed-width group body computes every lane (shape-stable executable)
    and this masked scatter discards the rest."""
    mask = jnp.asarray(lane_mask)

    def sel(o, n):
        m = mask.reshape(mask.shape + (1,) * (n.ndim - 1))
        return jnp.where(m, n, o)

    return jax.tree.map(sel, old, new)


def set_lane_state(stacked, lane: int, fresh):
    """Replace lane ``lane`` of a lane-stacked pytree with ``fresh``.

    The EAGER host-path lane write, used at lane REFILL: a retired lane's
    engine state (and latents / text embeddings) is overwritten with the
    next request's fresh state without touching the other in-flight lanes
    — static-index ``.at[lane].set`` update-slices (cheap to dispatch
    eagerly), no recompilation of the serving tick.  Inside compiled tick
    bodies use :func:`scatter_lane_states` / :func:`gather_lane_states` /
    :func:`merge_lane_states`, the traced-index generalizations."""
    return jax.tree.map(lambda s, f: s.at[lane].set(f), stacked, fresh)


def is_update_step(step: int, cfg: EngineConfig) -> bool:
    """Update/Dispatch phase of one step (warmup + every ``interval``).

    :func:`resolve_schedule` bakes this rule into the per-step ``mode``
    array of a :class:`~repro.core.schedule.SparsitySchedule`, which the
    single-scan sampler switches on; this Python predicate remains for
    host-side schedule construction and diagnostics.
    """
    m = cfg.mask
    if step < m.warmup_steps:
        return True
    return (step - m.warmup_steps) % m.interval == 0


# LRU-bounded (PR 4): a long-running server cycling distinct specs evicts
# the least-recently-resolved schedule instead of growing without limit.
# NOTE the coupling with the pipeline's sampler cache: evicting a schedule
# here means the next request with that spec resolves to a NEW schedule
# object, whose strategy identities miss the sampler cache and recompile —
# so this memo is sized ABOVE the sampler cache, never below.
_SCHEDULE_CACHE_SIZE = 128
_SCHEDULE_CACHE = LruCache(_SCHEDULE_CACHE_SIZE)


def schedule_cache_stats() -> dict:
    """Hit/miss/eviction counters of the schedule-resolution memo."""
    return _SCHEDULE_CACHE.stats()


def resolve_schedule(cfg: EngineConfig, num_steps: int, n_layers: int, *,
                     schedule=None, layer_strategies=None,
                     force_dense: bool = False):
    """Resolve the engine config into a canonical SparsitySchedule.

    ``EngineConfig.strategy`` / ``layer_strategies`` / ``mask.interval`` /
    ``mask.warmup_steps`` (and the ``EngineConfig.schedule`` named preset)
    collapse into one (step × layer) traced table — see
    :mod:`repro.core.schedule`.  An explicit ``schedule`` argument (name or
    prebuilt :class:`SparsitySchedule`) wins over everything.

    Resolution is MEMOIZED (LRU-bounded) for hashable specs (registry
    names + frozen configs) so repeated calls return the SAME schedule
    object — the sampler's jit cache keys on the schedule's strategy
    identities, and a stable resolution means the second request reuses
    the first request's compiled executable instead of re-tracing.

    Bucket-count auto-selection (``cfg.kv_buckets == 0``) happens at this
    resolution boundary too — :meth:`EngineConfig.resolved_kv_buckets`
    consults the calibration table per (strategy, config), so the chosen
    depth is frozen before any trace: one executable per configuration,
    and the serving ≤4-executable budget is unchanged (the candidate set
    {1, 2, 3} never multiplies executables — a config resolves to exactly
    one depth).
    """
    from repro.core.schedule import SparsitySchedule, get_schedule
    try:
        key = (cfg, num_steps, n_layers, schedule,
               None if layer_strategies is None else tuple(layer_strategies),
               force_dense)
        hash(key)
    except TypeError:
        key = None              # unhashable spec (ad-hoc objects): no memo
    if key is not None:
        cached = _SCHEDULE_CACHE.get(key)
        if cached is not None:
            return cached
    if schedule is not None and not force_dense:
        sched = get_schedule(schedule, cfg, num_steps, n_layers)
    else:
        sched = SparsitySchedule.from_config(cfg, num_steps, n_layers,
                                             layer_strategies=layer_strategies,
                                             force_dense=force_dense)
    if key is not None:
        _SCHEDULE_CACHE.put(key, sched)
    return sched


# ---------------------------------------------------------------------------
# Token-local pre-attention ops (Obs. 2: these commute with row skipping).
# ---------------------------------------------------------------------------

def rms_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-6) -> jax.Array:
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    return (x.astype(jnp.float32) * jax.lax.rsqrt(var + eps) * scale).astype(x.dtype)


def rope_freqs(n: int, dim: int, theta: float = 10000.0) -> jax.Array:
    inv = 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    t = jnp.arange(n, dtype=jnp.float32)
    return jnp.outer(t, inv)  # (n, dim//2)


def apply_rope(x: jax.Array, freqs: jax.Array) -> jax.Array:
    """x: (..., N, dh); freqs: (N, dh//2)."""
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    cos, sin = jnp.cos(freqs), jnp.sin(freqs)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).astype(x.dtype)


def _project_heads(x: jax.Array, w: jax.Array, heads: int) -> jax.Array:
    """(B, N, dm) @ (dm, H*dh) -> (B, H, N, dh)."""
    y = jnp.einsum("bnd,df->bnf", x, w)
    b, n = x.shape[:2]
    return y.reshape(b, n, heads, -1).transpose(0, 2, 1, 3)


def _qk(params: AttnParams, x: jax.Array, heads: int, freqs: Optional[jax.Array]):
    q = rms_norm(_project_heads(x, params.wq, heads), params.q_scale)
    k = rms_norm(_project_heads(x, params.wk, heads), params.k_scale)
    if freqs is not None:
        q, k = apply_rope(q, freqs), apply_rope(k, freqs)
    return q, k


def refresh_symbols(q: jax.Array, k: jax.Array, cfg: EngineConfig, n_text: int,
                    n_tokens: int) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """LEGACY seed §3.3 rule, kept verbatim as the bit-parity oracle.

    ``update_layer`` now calls the pluggable strategy resolved from
    ``cfg.strategy`` instead; ``tests/test_strategy.py`` asserts the
    ``"flashomni"`` strategy reproduces this function's packed symbols
    bit-for-bit.  Returns ``(s_c, s_s, m_c, m_s)`` — packed uint8 symbols
    plus the unpacked compressed-granularity masks (True = compute).
    """
    m = cfg.mask
    m_c = masklib.make_caching_mask(q, k, m, n_text)
    m_c = masklib.apply_degradation(m_c, m.degrade)
    # Static-capacity clamp on live blocks, ranked by total column mass.
    p_map = masklib.compressed_attention_map(q, k, m.pool)
    col_mass = jnp.sum(p_map, axis=-2)
    m_c = clamp_mask_topk(m_c, col_mass, cfg.cap_q_cmp(n_tokens))
    m_s = masklib.make_skip_mask(q, k, m, n_text)
    # Clamp per-row KV keeps to the compressed KV capacity (rank by mass).
    cap_kv = cfg.cap_kv_cmp(n_tokens)
    if cap_kv < m_s.shape[-1]:
        m_s = clamp_mask_topk(m_s, p_map, cap_kv)
    s_c = pack_bits(m_c)
    s_s = pack_bits(m_s.reshape(*m_s.shape[:-2], -1))
    return s_c, s_s, m_c, m_s


def _unpack(state: LayerState, cfg: EngineConfig, n_tokens: int):
    t = cfg.mask.n_blocks(n_tokens)
    m_c = unpack_bits(state.s_c, t)
    m_s = unpack_bits(state.s_s, t * t).reshape(*state.s_s.shape[:-1], t, t)
    return m_c, m_s


def plan_from_state(state: LayerState, cfg: EngineConfig,
                    n_tokens: int) -> DispatchPlan:
    """Legacy rebuild path: re-derive the DispatchPlan from the packed
    symbols (what every Dispatch step used to do).  Kept for the
    plan-reuse invariance tests and the amortization benchmark.  The
    stored ``row_score`` re-ranks the row-capacity truncation so the
    rebuilt plan matches the frozen one exactly."""
    m_c, m_s = _unpack(state, cfg, n_tokens)
    return build_dispatch_plan(m_c, m_s, cfg, n_tokens,
                               row_score=state.plan.row_score)


# ---------------------------------------------------------------------------
# Update / Dispatch step over one attention module.
# ---------------------------------------------------------------------------

def update_layer(
    params: AttnParams,
    x: jax.Array,
    state: LayerState,
    cfg: EngineConfig,
    *,
    n_text: int = 0,
    heads: int,
    freqs: Optional[jax.Array] = None,
    strategy: Optional[str | SparsityStrategy] = None,
    layer_idx: Optional[jax.Array] = None,
    strategy_id: Optional[jax.Array] = None,
    strategies: Optional[tuple] = None,
    step_idx: Optional[jax.Array] = None,
    num_steps: Optional[int | jax.Array] = None,
) -> tuple[jax.Array, LayerState]:
    """Full attention + symbol/cache refresh (paper *Update* phase).

    Two ways to pick the sparse-symbol producer:

      * static — resolved ONCE at trace time from ``cfg.strategy``
        (``strategy`` overrides it per call);
      * scheduled — ``strategies`` (a schedule's static active set) plus a
        TRACED ``strategy_id`` scalar, dispatched via
        :func:`~repro.core.strategy.emit_switch`.  This is how the scanned
        block body threads per-layer deployment tables without unrolling.

    ``layer_idx`` / ``step_idx`` (traced scalars under the model/pipeline
    scans) and ``num_steps`` (a static int, or a traced per-lane scalar
    under the batched serving ticks) reach the strategy's
    :class:`~repro.core.strategy.StrategyContext`.
    """
    b, n, dm = x.shape
    with jax.named_scope("fo.qkv"):
        q, k = _qk(params, x, heads, freqs)
        v = _project_heads(x, params.wv, heads)
    with jax.named_scope("fo.attention"):
        o = get_backend(cfg).dense_attention(q, k, v)          # (B,H,N,dh)
    ctx = StrategyContext(cfg=cfg, n_text=n_text, n_tokens=n,
                          layer_idx=layer_idx, step_idx=step_idx,
                          num_steps=num_steps)
    with jax.named_scope("fo.symbols"):
        if strategies is not None:
            sid = (jnp.zeros((), jnp.int32) if strategy_id is None
                   else strategy_id)
            syms = emit_switch(sid, q, k, ctx, strategies)
        else:
            strat = get_strategy(cfg.strategy if strategy is None
                                 else strategy)
            syms = strat.emit(q, k, ctx)
    s_c, s_s, m_c, m_s = syms.s_c, syms.s_s, syms.m_c, syms.m_s

    with jax.named_scope("fo.o_proj"):
        o_tok = o.transpose(0, 2, 1, 3)                        # (B,N,H,dh)
        dh = o_tok.shape[-1]
        wo_h = params.wo.reshape(heads, dh, dm)
        out = jnp.einsum("bnhd,hdf->bnf", o_tok, wo_h)

    with jax.named_scope("fo.cache"):
        m_ch = jnp.swapaxes(m_c, -1, -2)                       # (B, T, H)
        if cfg.cache_mode == "bias":
            bias = sparse_gemm.gemm_o_update_bias(o_tok, wo_h, m_ch,
                                                  block=cfg.mask.pool)
            taylor = taylorseer.update(state.taylor,
                                       bias.astype(cfg.cache_dtype))
        else:
            taylor = taylorseer.update(state.taylor,
                                       o.astype(cfg.cache_dtype))
    # Compile-once index plan: ALL index decoding for the coming Dispatch
    # steps happens here, amortized over the next interval−1 steps.  Rows
    # are ranked for the capacity truncation by the strategy's clamp
    # scores (column mass), summed over the heads where the row is live.
    with jax.named_scope("fo.plan"):
        row_score = jnp.sum(
            jnp.where(m_c, syms.q_scores.astype(jnp.float32), 0.0), axis=-2)
        plan = build_dispatch_plan(m_c, m_s, cfg, n, row_score=row_score)
    new_state = LayerState(s_c=s_c, s_s=s_s, taylor=taylor,
                           k_since=jnp.zeros((), jnp.int32), plan=plan)
    return out, new_state


def dispatch_layer(
    params: AttnParams,
    x: jax.Array,
    state: LayerState,
    cfg: EngineConfig,
    *,
    n_text: int = 0,
    heads: int,
    freqs: Optional[jax.Array] = None,
    plan: Optional[DispatchPlan] = None,
) -> tuple[jax.Array, LayerState]:
    """Sparse execution guided by the frozen DispatchPlan (paper *Dispatch*).

    Consumes ``state.plan`` verbatim — no symbol unpacking, mask expansion
    or top-k/argsort index work happens here; that all ran once inside
    :func:`update_layer`.  ``plan`` overrides the stored plan (used by the
    rebuild-vs-reuse benchmark and invariance tests).  Execution routes
    through :func:`repro.core.backend.get_backend` (XLA structural path or
    Pallas CSR kernels with compact GEMM-Q layout fusion).
    """
    b, n, dm = x.shape
    m = cfg.mask
    plan_stored = state.plan if plan is None else plan
    plan = plan_stored.widen()    # int16 id fields -> int32 for kernels/RoPE
    backend = get_backend(cfg)
    spec_c = cfg.caps(n)                                        # block granularity caps
    with jax.named_scope("fo.cache"):
        k_since = state.k_since + 1
        forecast = taylorseer.forecast(state.taylor, k_since, m.interval)

    # --- GEMM-Q: skip row blocks cached in every head (Obs. 2). ---
    with jax.named_scope("fo.qkv"):
        if cfg.use_gemm_q:
            q_flat = backend.gemm_q(x, params.wq, plan, block=m.pool)
            compact = backend.compact_q                         # (B, Cr·pool, H·dh)
        else:
            q_flat = jnp.einsum("bnd,df->bnf", x, params.wq)
            compact = False
        n_q = q_flat.shape[1]
        qh = q_flat.reshape(b, n_q, heads, -1).transpose(0, 2, 1, 3)
        qh = rms_norm(qh, params.q_scale)
        k_h = rms_norm(_project_heads(x, params.wk, heads), params.k_scale)
        if freqs is not None:
            q_freqs = freqs
            if compact:
                # Compact rows are gathered: RoPE phases follow the
                # ORIGINAL token positions of the gathered live rows.
                pos = (plan.row_ids[..., :, None] * m.pool
                       + jnp.arange(m.pool)).reshape(b, n_q)    # (B, Cr·pool)
                q_freqs = freqs[pos][:, None]                   # (B,1,n_q,dh/2)
            qh, k_h = apply_rope(qh, q_freqs), apply_rope(k_h, freqs)
        v_h = _project_heads(x, params.wv, heads)

    # --- Attention: backend sparse path over the frozen plan. ---
    dh = qh.shape[-1]
    with jax.named_scope("fo.attention"):
        if cfg.cache_mode == "bias":
            o_reuse = jnp.zeros((b, heads, n, dh), qh.dtype)
        else:
            o_reuse = forecast.astype(qh.dtype)
        o = backend.attention(qh, k_h, v_h, o_reuse, plan, spec_c,
                              compact_q=compact)

    # --- GEMM-O: live heads + forecast bias (Obs. 3, Eq. 4). ---
    with jax.named_scope("fo.o_proj"):
        o_tok = o.transpose(0, 2, 1, 3)
        wo_h = params.wo.reshape(heads, dh, dm)
        if cfg.cache_mode == "bias":
            bias_f = forecast.astype(x.dtype)
            if cfg.use_gemm_o:
                out = backend.gemm_o(o_tok, wo_h, plan, bias_f, block=m.pool,
                                     spec=spec_c)
            else:
                # Dense GEMM over (zero-filled) cached heads + forecast
                # bias — numerically identical, no FLOP saving (fidelity
                # fallback).
                m_tok = jnp.repeat(plan.m_ch, m.pool, axis=-2)[..., :n, :]
                out = jnp.einsum("bnhd,hdf->bnf",
                                 jnp.where(m_tok[..., None], o_tok, 0),
                                 wo_h) + bias_f
        else:
            out = jnp.einsum("bnhd,hdf->bnf", o_tok, wo_h)
    new_state = LayerState(s_c=state.s_c, s_s=state.s_s, taylor=state.taylor,
                           k_since=k_since, plan=plan_stored)
    return out, new_state

"""Compile-once DispatchPlan — precomputed CSR index plan for Dispatch steps.

The paper's Update–Dispatch engine (§3.2) freezes the sparse symbols at an
*Update* step and reuses them for the next ``𝒩−1`` *Dispatch* steps.  The
seed implementation froze only the PACKED symbols and re-derived every
index structure (``unpack_bits`` → block-mask expand → ``clamp_mask_topk``
→ ``active_indices``) on every dispatch of every layer — per-step work that
Sparse VideoGen / Sparse-vDiT show should be off the critical path.

:class:`DispatchPlan` moves all of that to Update time.  It is a plain
pytree carried inside ``LayerState``, so it flows through ``jit``/``scan``
and sharding unchanged, and every backend (XLA structural or Pallas CSR
kernels) consumes it verbatim:

  * ``q_ids``/``q_cnt``       — live q-block ids at kernel-block granularity
    (the attention spatial gather, symbol ``S_c``).
  * ``q_slots``               — the same live q blocks, re-indexed into the
    COMPACT GEMM-Q output layout (``(Cr·pool, F)`` row-major), so the
    Pallas CSR attention kernel can read Q straight out of the compact
    projection without a scatter (layout fusion).
  * ``kv_ids``/``kv_cnt``/``pair_live`` — per-(batch, head) KV-block UNION
    with the exact (i, j) liveness inside the gathered subset (the XLA
    structural path's reduction layout, symbol ``S_s``).
  * ``kv_row_ids``/``kv_row_cnt``       — per-live-row CSR column lists
    (the Pallas kernel's reduction layout).
  * ``row_ids``/``row_cnt``   — pool-granularity row blocks live in ANY
    head (GEMM-Q spatial gather + GEMM-O spatial gather, Obs. 2).
  * ``head_ids``/``head_cnt``/``head_mask`` — per-live-row live-head lists
    (GEMM-O reduction sparsity, Obs. 3) in both CSR (Pallas) and mask
    (XLA) form.
  * ``m_ch``                  — the compressed (row-block, head) compute
    mask, kept for the dense fidelity fallbacks and diagnostics.

All shapes are static functions of ``(EngineConfig, n_tokens, heads)``, so
a Dispatch step's jaxpr contains no sort/top-k/unpack work at all — see
the jaxpr-inspection test in ``tests/test_backend.py``.

Row-capacity truncation ranks by COLUMN MASS: ``row_score`` (the per-row
attention mass the strategy's capacity clamp used, summed over live heads)
decides which live rows survive when ``cap_q_frac`` truncates — the
lowest-mass rows degrade to cache-reuse first.  The score is carried in
the plan so the legacy rebuild path (:func:`~repro.core.engine.
plan_from_state`) reproduces the exact same truncation.

Plan memory (HunyuanVideo 33K-token scale): every block-id index field —
``kv_row_ids``/``row_ids`` plus ``q_ids``/``q_slots``/``kv_ids`` and the
bucketed ``bkt_*`` id buffers — is stored as int16 whenever every block
index fits in 15 bits (33K tokens / 64-token blocks = 516 blocks, far
under 2¹⁵) and widened to int32 on use via :meth:`DispatchPlan.widen`,
halving the dominant plan buffers.

Occupancy buckets (``EngineConfig.kv_buckets > 1``): the ``bkt_*`` fields
re-sort the H·Cq (head, q-slot) layout rows into a static set of
halving-width KV buckets (:func:`bucket_geometry`) so the Pallas kernel
grid covers live *work* instead of live *rows* — a row with 3 live KV
blocks occupies a ≈3-wide reduction, not a ``cap_kv``-wide one.  Bucket
truncation is scattered back into ``kv_row_cnt`` so the uniform kernel
and the XLA per-row CSR path consume identical truncated lists (the PR-4
shared-truncation invariant, extended to buckets).

GEMM-O buckets (ISSUE 8 tentpole): the same treatment for the OUTPUT
projection's reduction axis.  The ``gmo_*`` fields sort the ``Cr`` compact
row slots by LIVE-HEAD count into :func:`bucket_geometry` buckets over the
head axis (``bucket_geometry(Cr, H, 1, kv_buckets)``), so a row with one
live head occupies a 1-deep reduction slot instead of the uniform grid's
``Hc``-deep one — the paper's GEMM-O 2.5–3.8× comes from exactly this
skew.  Any bucket-induced head clamp is folded BACK into
``head_cnt``/``head_mask`` before extraction (:func:`gmo_layout`), so the
bucketed kernel, the uniform kernel, and the XLA masked-einsum path all
consume the same truncated head lists — bit-identical outputs.

``occ_hist`` (always emitted) is the Update-time KV-occupancy histogram
over halving width classes (:func:`occupancy_histogram`) — the signal
``benchmarks/autotune.py`` calibrates and ``kernels/tuning.py``'s cost
model consumes to pick ``kv_buckets`` per (strategy, config) at
schedule-resolution time.  It is a pure function of the plan's final
``kv_row_cnt``, so ``plan_from_state`` rebuilds it bit-exactly.
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import masks as masklib
from repro.core.attention import attention_plan_indices
from repro.core.symbols import active_indices, clamp_mask_topk, slot_positions

__all__ = [
    "DispatchPlan",
    "build_dispatch_plan",
    "empty_plan_like",
    "bucket_geometry",
    "bucket_slot_layout",
    "bucket_grid_slots",
    "bucket_layout",
    "gmo_layout",
    "csr_path",
    "csr_windows_at",
    "live_work",
    "occupancy_histogram",
    "OCC_BINS",
]

#: Width classes of the occupancy histogram carried in ``DispatchPlan.
#: occ_hist`` — class ``i`` holds live rows whose KV list fits width
#: ``⌈cap_kv/2^{i+1}⌉`` (class 0 = needs more than half the capacity).
OCC_BINS = 8


def occupancy_histogram(kv_row_cnt: jax.Array, q_cnt: jax.Array,
                        cap_kv: int) -> jax.Array:
    """Per-sample halving-width-class histogram of live-row KV occupancy.

    ``kv_row_cnt`` (B, H, Cq) int32, ``q_cnt`` (B, H) int32 →
    (B, :data:`OCC_BINS`) int32.  A live row lands in class
    ``#{i : cnt ≤ ⌈cap_kv/2^{i+1}⌉}`` — 0 means it needs (more than) the
    full/half capacity, higher classes fit ever-narrower buckets, and the
    last class absorbs the near-empty tail (including count-0 rows).  A
    pure function of the plan's final (truncation-folded) counts, computed
    at Update time — Dispatch never touches it."""
    live = (jnp.arange(kv_row_cnt.shape[-1], dtype=jnp.int32)
            < q_cnt[..., None])                                # (B, H, Cq)
    ths = np.asarray([-(-cap_kv // (1 << (i + 1)))
                      for i in range(OCC_BINS - 1)], np.int32)
    cls = jnp.sum(kv_row_cnt[..., None] <= ths, axis=-1)       # 0..OCC_BINS-1
    onehot = (cls[..., None] == jnp.arange(OCC_BINS, dtype=cls.dtype)) \
        & live[..., None]
    return jnp.sum(onehot, axis=(1, 2)).astype(jnp.int32)      # (B, OCC_BINS)


def bucket_geometry(cap_q: int, cap_kv: int, heads: int,
                    n_buckets: int) -> tuple[tuple[int, int], ...]:
    """Static occupancy-bucket geometry: ``((rows, kv_width), ...)``.

    Buckets are ordered widest first; widths halve per bucket
    (``cap_kv, ⌈cap_kv/2⌉, ⌈cap_kv/4⌉, …``) and row capacities are
    allocated inversely to width (equal slot area per bucket) over the
    ``heads · cap_q`` layout rows — the head axis is folded into the row
    pool, because the skew the buckets exist to absorb (Sparse VideoGen's
    spatial/temporal split, ``hunyuan-1.5x``'s sliding-window heads) is
    ACROSS heads.  Total grid slots shrink from ``R · cap_kv`` (uniform)
    to ``R · cap_kv · B / (2^B − 1)`` — ``3/7 ≈ 0.43×`` at ``B = 3`` —
    a static bound independent of the plan's occupancy draw.
    """
    r_total = heads * cap_q
    n_buckets = max(1, min(n_buckets, r_total, cap_kv))
    if n_buckets == 1:
        return ((r_total, cap_kv),)
    widths = [-(-cap_kv // (1 << i)) for i in range(n_buckets)]
    denom = (1 << n_buckets) - 1
    rows = [max(1, (r_total << i) // denom) for i in range(n_buckets)]
    rows[-1] += r_total - sum(rows)
    # Tiny-R edge: the max(1,·) bumps can overdraw; repay from the
    # narrowest buckets that still have rows to spare.
    for i in range(n_buckets - 1, -1, -1):
        if rows[i] < 1:
            for j in range(n_buckets - 1, -1, -1):
                if rows[j] > 1:
                    take = min(rows[j] - 1, 1 - rows[i])
                    rows[j] -= take
                    rows[i] += take
                    if rows[i] >= 1:
                        break
    assert sum(rows) == r_total and all(r >= 1 for r in rows)
    return tuple(zip(rows, widths))


def bucket_slot_layout(geometry) -> tuple[np.ndarray, np.ndarray,
                                          np.ndarray, np.ndarray]:
    """Flatten a bucket geometry into per-grid-slot static index arrays.

    Returns ``(srow, j_of, soff, slast)`` — all int32 of length
    ``S = Σ rows·width``: the layout row owning each slot, the slot's
    j-position within its row's KV reduction, the slot index where the
    row's reduction starts, and a 0/1 last-slot-of-row flag.  These are
    compile-time constants of the geometry; the kernel scalar-prefetches
    them to drive its two-level (bucket × row × per-bucket-Ckv) grid.
    """
    srow, j_of, soff, slast = [], [], [], []
    r = 0
    s = 0
    for rows, width in geometry:
        for _ in range(rows):
            for j in range(width):
                srow.append(r)
                j_of.append(j)
                soff.append(s)
                slast.append(1 if j == width - 1 else 0)
            r += 1
            s += width
    mk = lambda a: np.asarray(a, np.int32)
    return mk(srow), mk(j_of), mk(soff), mk(slast)


def bucket_grid_slots(geometry) -> int:
    """Total kernel grid slots the bucketed layout occupies."""
    return int(sum(rows * width for rows, width in geometry))


def bucket_layout(q_ids, q_cnt, q_slots, kv_row_ids, kv_row_cnt,
                  row_score_q, geometry, t_q: int):
    """Sort the H·Cq (head, q-slot) layout rows into the bucket geometry.

    All index arrays are (B, H, Cq[, Ck]) int32 as produced by
    :func:`~repro.core.attention.attention_plan_indices` +
    :func:`~repro.core.symbols.active_indices`; ``row_score_q`` is a
    (B, H, Cq) per-q-row ranking score.  Returns ``(bkt, kv_row_cnt')``:
    the ``bkt_*`` field dict of :class:`DispatchPlan` and the per-row
    counts with the bucket truncation folded back in (shared-truncation
    invariant — uniform kernel and XLA path consume the same lists).

    Runs at Update time only (it sorts); a Dispatch step consumes the
    emitted layout verbatim.
    """
    b_, h_, cq = q_ids.shape
    r_tot = h_ * cq
    live = jnp.arange(cq, dtype=jnp.int32) < q_cnt[..., None]      # (B,H,Cq)
    cnt = jnp.where(live, kv_row_cnt, 0)
    flat2 = lambda a: a.reshape(b_, r_tot)
    pid = jnp.broadcast_to(jnp.arange(r_tot, dtype=jnp.int32), (b_, r_tot))
    # Deterministic lexicographic sort: live first, then descending KV
    # count, then descending row mass, pair id as the tie-break — the pid
    # operand doubles as the permutation (plan_from_state must rebuild
    # this layout bit-exactly from the stored row_score).
    *_, order = jax.lax.sort(
        (flat2(~live).astype(jnp.int32), flat2(-cnt),
         flat2(-row_score_q.astype(jnp.float32)), pid), num_keys=4)
    g = lambda a: jnp.take_along_axis(flat2(a), order, axis=-1)
    s_live = g(live.astype(jnp.int32)) > 0                         # (B, R)
    # Per-position bucket widths (static) and the row_score-consistent
    # truncation: among equal counts the higher-mass row lands in the
    # wider slot, so the lowest-mass rows truncate first.
    w_pos = np.concatenate([np.full(r, w, np.int32) for r, w in geometry])
    bkt_kv_cnt = jnp.minimum(g(cnt), w_pos)
    # Scatter the bucket truncation back into the per-row counts so the
    # uniform kernel and the XLA per-row CSR path see the SAME truncated
    # lists — bucketed and uniform compute the same tiles, no carve-outs.
    new_cnt = jnp.put_along_axis(jnp.zeros_like(flat2(cnt)), order,
                                 bkt_kv_cnt, axis=-1,
                                 inplace=False).reshape(b_, h_, cq)
    last_cnt = jnp.take_along_axis(
        new_cnt, jnp.maximum(q_cnt - 1, 0)[..., None], axis=-1)
    # Padding q slots duplicate the last live row; give them its truncated
    # count too, or their recompute would clobber the live block's output
    # with the untruncated reduction.
    kv_row_cnt = jnp.where(live, new_cnt, last_cnt)
    srow_np, jof_np, _, _ = bucket_slot_layout(geometry)
    ck = kv_row_ids.shape[-1]
    sorted_kv = jnp.take_along_axis(
        kv_row_ids.reshape(b_, r_tot, ck), order[..., None], axis=-2)
    bkt = dict(
        bkt_head=(order // cq).astype(jnp.int32),
        bkt_q_ids=jnp.where(s_live, g(q_ids), t_q),
        bkt_q_src=jnp.where(s_live, g(q_ids), 0),
        bkt_q_slots=jnp.where(s_live, g(q_slots), 0),
        bkt_kv_ids=sorted_kv[:, srow_np, jof_np],                  # (B, S)
        bkt_kv_cnt=bkt_kv_cnt,
    )
    return bkt, kv_row_cnt


def gmo_layout(row_ids, row_cnt, head_ids, head_cnt, row_score_r, geometry,
               t_cmp: int):
    """Sort the ``Cr`` compact row slots into live-head-count buckets.

    The GEMM-O analogue of :func:`bucket_layout`: ``geometry`` comes from
    ``bucket_geometry(Cr, H, 1, kv_buckets)`` (layout rows = compact row
    slots, reduction axis = live heads).  ``row_score_r`` is the (B, Cr)
    row-mass score gathered at ``row_ids`` — among equal head counts the
    higher-mass row lands in the wider slot, mirroring the attention sort.

    Returns ``(gmo, head_cnt', head_mask')`` where the ``gmo_*`` dict
    feeds :class:`DispatchPlan` and the primed lists carry any
    bucket-induced head clamp folded BACK in: ``head_cnt'`` is the clamp
    scattered to slot order and ``head_mask'`` is rebuilt from the clamped
    CSR prefixes, so the uniform kernel (which iterates ``hh <
    head_cnt``) and the XLA masked einsum consume the SAME truncated head
    lists as the bucketed kernel — bit-identical, no carve-outs.  Runs at
    Update time only (it sorts)."""
    b_, cr = row_ids.shape
    h_ = head_ids.shape[-1]
    slot = jnp.arange(cr, dtype=jnp.int32)
    live = slot[None, :] < row_cnt[:, None]                        # (B, Cr)
    cnt = jnp.where(live, head_cnt, 0)
    pid = jnp.broadcast_to(slot, (b_, cr))
    *_, order = jax.lax.sort(
        ((~live).astype(jnp.int32), -cnt,
         -row_score_r.astype(jnp.float32), pid), num_keys=4)
    g = lambda a: jnp.take_along_axis(a, order, axis=-1)
    s_live = g(live.astype(jnp.int32)) > 0                         # (B, R)
    w_pos = np.concatenate([np.full(r, w, np.int32) for r, w in geometry])
    gmo_head_cnt = jnp.minimum(g(cnt), w_pos)
    new_cnt = jnp.put_along_axis(jnp.zeros_like(cnt), order, gmo_head_cnt,
                                 axis=-1, inplace=False)
    # Rebuild head_mask from the clamped CSR prefixes (the ids are exactly
    # the ascending True positions, so an unclamped rebuild is the
    # identity) — XLA's masked einsum then matches the clamp too.
    keep = jnp.arange(h_, dtype=jnp.int32) < new_cnt[..., None]    # (B,Cr,H)
    sid = jnp.where(keep, head_ids, h_)
    new_mask = jnp.put_along_axis(
        jnp.zeros((b_, cr, h_ + 1), jnp.bool_), sid,
        jnp.ones_like(sid, jnp.bool_), axis=-1, inplace=False)[..., :h_]
    srow_np, jof_np, _, _ = bucket_slot_layout(geometry)
    sorted_heads = jnp.take_along_axis(head_ids, order[..., None], axis=-2)
    gmo = dict(
        gmo_rows=jnp.where(s_live, g(row_ids), t_cmp),
        gmo_src=jnp.where(s_live, g(row_ids), 0),
        gmo_head_ids=sorted_heads[:, srow_np, jof_np],             # (B, S)
        gmo_head_cnt=gmo_head_cnt,
    )
    return gmo, new_cnt, new_mask


class DispatchPlan(NamedTuple):
    """Precomputed index plan for Dispatch steps (a pytree of int32/bool)."""

    # --- attention, kernel-block granularity, per (B, H) ---
    q_ids: jax.Array       # (B, H, Cq) int32 live q-block ids (full layout)
    q_cnt: jax.Array       # (B, H)     int32
    q_slots: jax.Array     # (B, H, Cq) int32 same blocks, compact layout
    kv_ids: jax.Array      # (B, H, Ck) int32 KV-union ids (XLA path)
    kv_cnt: jax.Array      # (B, H)     int32
    pair_live: jax.Array   # (B, H, Cq, Ck) bool exact (i,j) mask in the union
    kv_row_ids: jax.Array  # (B, H, Cq, Ck) int16/int32 per-row CSR (Pallas)
    kv_row_cnt: jax.Array  # (B, H, Cq) int32
    # --- GEMM-Q / GEMM-O, pool granularity, per B ---
    row_ids: jax.Array     # (B, Cr) int16/int32 row blocks live in any head
    row_cnt: jax.Array     # (B,)    int32
    head_ids: jax.Array    # (B, Cr, H) int32 live heads per live row (CSR)
    head_cnt: jax.Array    # (B, Cr) int32
    head_mask: jax.Array   # (B, Cr, H) bool gathered (row, head) mask
    m_ch: jax.Array        # (B, T, H) bool compressed compute mask
    row_score: jax.Array   # (B, T) f32 column-mass row ranking (truncation)
    # --- Update-time KV-occupancy histogram (always emitted) ---
    # (B, OCC_BINS) int32 live rows per halving width class; the
    # autotuner's calibration signal (see kernels/tuning.py).
    occ_hist: Optional[jax.Array] = None
    # --- occupancy-bucketed CSR layout (None unless cfg.kv_buckets > 1) ---
    # Layout rows fold the head axis: R = H·Cq (head, q-slot) pairs sorted
    # by (live, kv count, row_score), widest bucket first; see
    # :func:`bucket_geometry`.  S = Σ rows·width grid slots.
    bkt_head: Optional[jax.Array] = None     # (B, R) int32 head of layout row
    bkt_q_ids: Optional[jax.Array] = None    # (B, R) output q block (dead→T_q)
    bkt_q_src: Optional[jax.Array] = None    # (B, R) read q block, full layout
    bkt_q_slots: Optional[jax.Array] = None  # (B, R) read q block, compact
    bkt_kv_ids: Optional[jax.Array] = None   # (B, S) per-slot kv-block id
    bkt_kv_cnt: Optional[jax.Array] = None   # (B, R) bucket-truncated count
    # --- GEMM-O head-count buckets (None unless cfg.kv_buckets > 1) ---
    # Layout rows are the Cr compact row slots sorted by live-head count
    # into bucket_geometry(Cr, H, 1, kv_buckets); S = Σ rows·width grid
    # slots.  See :func:`gmo_layout`.
    gmo_rows: Optional[jax.Array] = None      # (B, Cr) write row id (dead→T)
    gmo_src: Optional[jax.Array] = None       # (B, Cr) read row id (dead→0)
    gmo_head_ids: Optional[jax.Array] = None  # (B, S) per-slot head id
    gmo_head_cnt: Optional[jax.Array] = None  # (B, Cr) clamped live-head cnt
    # --- plan-sharded mesh partition (None unless cfg.mesh_sp > 1 with
    # mesh_axis == "seq"; see distributed/plan_shard.py).  Axis P indexes
    # the destination shard of the (data, seq) mesh; Cqs/Cks/pc are the
    # static per-shard row / union / per-pair capacities of ShardGeometry.
    shd_q_ids: Optional[jax.Array] = None      # (B,H,P,Cqs) shard-LOCAL q blocks
    shd_q_src: Optional[jax.Array] = None      # (B,H,P,Cqs) same, full layout
    shd_q_slots: Optional[jax.Array] = None    # (B,H,P,Cqs) same, compact layout
    shd_q_cnt: Optional[jax.Array] = None      # (B,H,P)
    shd_kv_ids: Optional[jax.Array] = None     # (B,H,P,Cks) union, GLOBAL ids
    shd_kv_cnt: Optional[jax.Array] = None     # (B,H,P)
    shd_kv_row_ids: Optional[jax.Array] = None  # (B,H,P,Cqs,Ck) union-slot CSR
    shd_kv_row_cnt: Optional[jax.Array] = None  # (B,H,P,Cqs)
    shd_gather_idx: Optional[jax.Array] = None  # (B,H,P,Cks) buffer placement
    shd_send_ids: Optional[jax.Array] = None   # (B,H,Psrc,Pdst,pc) local ids
    shd_send_cnt: Optional[jax.Array] = None   # (B,H,Psrc,Pdst)

    def widen(self) -> "DispatchPlan":
        """Return a plan with the compact int16 id fields widened to int32.

        Called once at Dispatch entry (and idempotent): kernels, gathers
        and position arithmetic (RoPE ``row_ids · pool + offset`` can exceed
        int16 at 33K tokens) always see int32 ids, while the stored plan
        keeps the narrow dtype.
        """
        if self.kv_row_ids.dtype == jnp.int32 and self.row_ids.dtype == jnp.int32 \
                and self.q_ids.dtype == jnp.int32:
            return self
        w = lambda a: (a if a is None or a.dtype == jnp.int32
                       else a.astype(jnp.int32))
        return self._replace(
            q_ids=w(self.q_ids), q_slots=w(self.q_slots), kv_ids=w(self.kv_ids),
            kv_row_ids=w(self.kv_row_ids), row_ids=w(self.row_ids),
            head_ids=w(self.head_ids),
            bkt_head=w(self.bkt_head), bkt_q_ids=w(self.bkt_q_ids),
            bkt_q_src=w(self.bkt_q_src), bkt_q_slots=w(self.bkt_q_slots),
            bkt_kv_ids=w(self.bkt_kv_ids),
            gmo_rows=w(self.gmo_rows), gmo_src=w(self.gmo_src),
            gmo_head_ids=w(self.gmo_head_ids),
            shd_q_ids=w(self.shd_q_ids), shd_q_src=w(self.shd_q_src),
            shd_q_slots=w(self.shd_q_slots), shd_kv_ids=w(self.shd_kv_ids),
            shd_kv_row_ids=w(self.shd_kv_row_ids),
            shd_gather_idx=w(self.shd_gather_idx),
            shd_send_ids=w(self.shd_send_ids))


def build_dispatch_plan(m_c: jax.Array, m_s: jax.Array, cfg, n_tokens: int,
                        row_score: Optional[jax.Array] = None,
                        compact_ids: bool = True) -> DispatchPlan:
    """Derive the full index plan from fresh compressed-granularity masks.

    ``m_c``: (B, H, T) bool, ``m_s``: (B, H, T, T) bool — True = compute,
    as produced by a :class:`~repro.core.strategy.SparsityStrategy`.  Runs
    ONCE per Update step; every sort/top-k in the engine lives here.

    ``row_score`` (B, T) ranks rows for the capacity truncation (column
    mass from the strategy's ``q_scores``); when ``None`` it falls back to
    the mask-derived live-pair mass (the rebuild path reads the stored
    score instead, so frozen vs rebuilt plans stay identical).
    ``compact_ids=False`` disables the int16 id compaction (round-trip
    reference in tests).
    """
    m = cfg.mask
    spec = cfg.caps(n_tokens)
    factor = m.pool // m.block_q
    t_q = -(-n_tokens // m.block_q)
    t_kv = -(-n_tokens // m.block_kv)
    t_cmp = m_c.shape[-1]

    # Kernel-block granularity masks (transient — not stored).
    # GEMM-Q / GEMM-O spatial gather first (pool granularity, any-head
    # union): attention may only compute q blocks whose pool row survived
    # the row-capacity truncation — the row projection simply does not
    # exist for the others (they degrade to cache-reuse, consistently
    # across backends; the seed XLA path silently attended with q = 0).
    cap_rows = cfg.cap_q_cmp(n_tokens)
    row_live = jnp.any(m_c, axis=-2)                               # (B, T)
    if row_score is None:
        # Mask-derived column-mass proxy: live (head, kv-block) pairs per
        # row — rows doing the least live work are dropped first.
        row_score = jnp.sum(
            jnp.where(m_c, jnp.sum(m_s, axis=-1).astype(jnp.float32), 0.0),
            axis=-2)
    row_score = row_score.astype(jnp.float32)
    # Ranked truncation (ROADMAP item): keep the top-`cap` rows by column
    # mass, not the first `cap` in index order; `active_indices` then
    # restores ascending id order for DMA-friendly gathers.
    row_live = clamp_mask_topk(row_live, row_score, cap_rows)
    row_ids, row_cnt = active_indices(row_live, cap_rows)
    slot = jnp.arange(cap_rows, dtype=jnp.int32)
    sid = jnp.where(slot < row_cnt[..., None], row_ids, t_cmp)
    kept = jnp.zeros((*row_ids.shape[:-1], t_cmp + 1), jnp.bool_)
    kept = jnp.put_along_axis(kept, sid, jnp.ones_like(sid, jnp.bool_),
                              axis=-1, inplace=False)[..., :t_cmp]
    m_c = m_c & kept[..., None, :]                                 # (B, H, T)

    m_c_blk = masklib.expand_block_mask(m_c, factor, t_q)
    m_s_blk = jnp.repeat(jnp.repeat(m_s, factor, axis=-2),
                         m.pool // m.block_kv, axis=-1)[..., :t_q, :t_kv]

    # Attention spatial gather (S_c) + XLA reduction layout (per-(b, h)
    # KV union over live rows) — shared with the mask-level
    # ``sparse_attention_xla`` entry so both paths rank/clamp identically.
    q_ids, q_cnt, kv_ids, kv_cnt, pair_live = attention_plan_indices(
        m_c_blk, m_s_blk, spec)

    # Pallas reduction layout: per-live-row CSR column lists.
    rows = jnp.take_along_axis(m_s_blk, q_ids[..., :, None], axis=-2)
    # Plan-sharded mesh fold (distributed/plan_shard.py): the per-(src,
    # dst) shipped-block clamp is applied to the ROW MASKS before the
    # lists are extracted — shared truncation, so every backend (sharded
    # or the single-device oracle) consumes identical lists.  With
    # pair_cap at its safe bound this is the identity and the plan below
    # matches the non-mesh build bit-for-bit.
    geom = None
    mesh_sp = getattr(cfg, "mesh_sp", 1)
    if mesh_sp > 1 and getattr(cfg, "mesh_axis", "seq") == "seq":
        from repro.distributed.plan_shard import mesh_keep_rows, shard_geometry
        geom = shard_geometry(spec, t_q, t_kv, mesh_sp,
                              getattr(cfg, "mesh_pair_slack", 1.5))
        rows = mesh_keep_rows(rows, q_ids, q_cnt, geom)
    kv_row_ids, kv_row_cnt = active_indices(rows, spec.cap_kv)

    # Compact-layout remap (needed below by the bucketed layout too): live
    # q block i (block granularity) lives at block index
    # slot(i // factor)·factor + i % factor of the compact (Cr·pool, F)
    # GEMM-Q output.  Live q blocks always fall inside live rows.
    row_slot = slot_positions(row_ids, row_cnt, t_cmp)             # (B, T)
    slot_of = jnp.take_along_axis(
        jnp.broadcast_to(row_slot[:, None, :], (*q_ids.shape[:-1], t_cmp)),
        q_ids // factor, axis=-1)
    q_slots = slot_of * factor + q_ids % factor

    # Occupancy-bucketed layout (ISSUE 6 tentpole): sort the H·Cq
    # (head, q-slot) layout rows by KV occupancy into the static bucket
    # geometry so the kernel grid covers live WORK, not live rows.  The
    # sort runs here — Update time — so Dispatch jaxprs stay sort-free.
    bkt = {}
    if getattr(spec, "kv_buckets", 1) > 1:
        b_, h_, _ = q_ids.shape
        geometry = bucket_geometry(spec.cap_q, spec.cap_kv, h_,
                                   spec.kv_buckets)
        score = jnp.take_along_axis(
            jnp.broadcast_to(row_score[:, None, :], (b_, h_, t_cmp)),
            (q_ids // factor).astype(jnp.int32), axis=-1)
        bkt, kv_row_cnt = bucket_layout(
            q_ids, q_cnt, q_slots, kv_row_ids, kv_row_cnt, score,
            geometry, t_q)

    # Per-shard partition + collective schedule, emitted AFTER every
    # truncation (pair clamp above, bucket layout here) has been folded
    # into kv_row_cnt — the partition consumes final lists and never
    # truncates on its own (see plan_shard.partition_plan).
    shd = {}
    if geom is not None:
        from repro.distributed.plan_shard import partition_plan
        shd = partition_plan(q_ids, q_cnt, q_slots, kv_row_ids, kv_row_cnt,
                             t_kv, geom)

    # GEMM-O reduction sparsity over the kept rows.  Padding slots (slot >=
    # row_cnt) duplicate the last live row id; their head lists MUST be
    # empty — the Pallas GEMM-O output is bias-aliased, so on real TPU a
    # padded duplicate with live heads would re-accumulate that row's
    # contribution once per padded slot (interpret mode hides this).
    m_ch = jnp.swapaxes(m_c, -1, -2)                               # (B, T, H)
    row_valid = slot < row_cnt[..., None]                          # (B, Cr)
    head_mask = jnp.take_along_axis(m_ch, row_ids[..., None], axis=-2)
    head_mask = head_mask & row_valid[..., None]
    heads = m_ch.shape[-1]
    head_ids, head_cnt = active_indices(head_mask, heads)

    # GEMM-O head-count buckets (ISSUE 8 tentpole): sort the Cr compact
    # row slots by live-head count into halving-depth buckets so the
    # output projection's grid covers live head-work, not Cr·Hc worst
    # case.  Any bucket head clamp is folded back into head_cnt/head_mask
    # (shared truncation — uniform kernel and XLA path stay bit-identical
    # to the bucketed kernel).
    gmo = {}
    if getattr(spec, "kv_buckets", 1) > 1:
        geometry_o = bucket_geometry(cap_rows, heads, 1, spec.kv_buckets)
        score_rows = jnp.take_along_axis(row_score, row_ids, axis=-1)
        gmo, head_cnt, head_mask = gmo_layout(
            row_ids, row_cnt, head_ids, head_cnt, score_rows, geometry_o,
            t_cmp)

    # Occupancy histogram — computed from the FINAL (truncation-folded)
    # counts so plan_from_state rebuilds it bit-exactly.
    occ_hist = occupancy_histogram(kv_row_cnt, q_cnt, spec.cap_kv)

    # Plan-memory compaction: every block-id buffer fits in 15 bits at any
    # realistic scale (33K tokens / 64-token blocks = 516 blocks); store
    # int16, widen()ed to int32 on use.  ``q_ids``/``q_slots``/``kv_ids``
    # join ``kv_row_ids``/``row_ids`` (ISSUE 6 satellite); ``head_ids``
    # and the ``gmo_*`` ids join in ISSUE 8 (head ids < H and gmo row ids
    # ≤ t_cmp both clear the same 15-bit gate).
    if compact_ids and max(t_cmp, t_q + 1, t_kv, heads) < 2 ** 15:
        narrow = lambda a: a.astype(jnp.int16)
        kv_row_ids = narrow(kv_row_ids)
        row_ids = narrow(row_ids)
        q_ids = narrow(q_ids)
        q_slots = narrow(q_slots)
        kv_ids = narrow(kv_ids)
        head_ids = narrow(head_ids)
        if bkt:
            for key in ("bkt_head", "bkt_q_ids", "bkt_q_src", "bkt_q_slots",
                        "bkt_kv_ids"):
                bkt[key] = narrow(bkt[key])
        if gmo:
            for key in ("gmo_rows", "gmo_src", "gmo_head_ids"):
                gmo[key] = narrow(gmo[key])
        # shd_gather_idx indexes the KV exchange buffer, which can hold up
        # to buf_blocks > t_kv entries — gate its compaction separately.
        if shd and geom.buf_blocks < 2 ** 15:
            for key in ("shd_q_ids", "shd_q_src", "shd_q_slots", "shd_kv_ids",
                        "shd_kv_row_ids", "shd_gather_idx", "shd_send_ids"):
                shd[key] = narrow(shd[key])

    plan = DispatchPlan(
        q_ids=q_ids, q_cnt=q_cnt, q_slots=q_slots,
        kv_ids=kv_ids, kv_cnt=kv_cnt, pair_live=pair_live,
        kv_row_ids=kv_row_ids, kv_row_cnt=kv_row_cnt,
        row_ids=row_ids, row_cnt=row_cnt,
        head_ids=head_ids, head_cnt=head_cnt, head_mask=head_mask,
        m_ch=m_ch, row_score=row_score, occ_hist=occ_hist,
        **bkt, **gmo, **shd,
    )
    # Opt-in debug hook (EngineConfig.validate_plans / REPRO_VALIDATE_
    # PLANS=1): structurally validate the freshly built plan on host.
    # cfg/n_tokens are statics, so the callback closes over them; the
    # checker tolerates any stacked lane/layer axes vmap may add.
    from repro.analysis.plan_check import validation_enabled
    if validation_enabled(cfg):
        from repro.analysis.plan_check import hook_validate
        jax.debug.callback(
            lambda p: hook_validate(p, cfg, n_tokens), plan)
    return plan


def _csr_kv_len(cfg, n_tokens: int) -> int:
    """Keys each uniform CSR call reads: the whole sequence, or on a
    sequence-sharded mesh the per-shard buffer (union blocks plus one pad
    block)."""
    if getattr(cfg, "mesh_sp", 1) > 1 and getattr(cfg, "mesh_axis",
                                                  "seq") == "seq":
        from repro.distributed.plan_shard import shard_geometry
        spec = cfg.caps(n_tokens)
        t = -(-n_tokens // spec.block_kv)
        geom = shard_geometry(spec, t, t, cfg.mesh_sp,
                              getattr(cfg, "mesh_pair_slack", 1.5))
        return (geom.cap_kv + 1) * spec.block_kv
    return n_tokens


def csr_windows_at(cfg, n_tokens: int, head_dim: int, dtype) -> int:
    """The VMEM windows the uniform CSR kernel cuts a head's K and V into
    at these shapes (``kernels.flashomni_attention.csr_windows``)."""
    from repro.kernels.flashomni_attention import csr_windows
    return csr_windows(_csr_kv_len(cfg, n_tokens), head_dim,
                       jnp.dtype(dtype).itemsize, cfg.mask.block_kv)


def csr_path(cfg, n_tokens: int, head_dim: int, dtype) -> str:
    """The CSR attention kernel a Dispatch step runs at these shapes:
    ``"bucketed"`` (``kv_buckets`` > 1 on one device), else ``"resident"``
    (K and V of a head in one VMEM window) or ``"windowed"``
    (:func:`csr_windows_at` > 1)."""
    seq_mesh = getattr(cfg, "mesh_sp", 1) > 1 and getattr(
        cfg, "mesh_axis", "seq") == "seq"
    if not seq_mesh and cfg.caps(n_tokens).kv_buckets > 1:
        return "bucketed"
    return ("resident" if csr_windows_at(cfg, n_tokens, head_dim, dtype) == 1
            else "windowed")


def live_work(plan: DispatchPlan
              ) -> dict[str, tuple[jax.Array, jax.Array | int]]:
    """Live work against launched grid slots of the three Dispatch kernels,
    and how full the CSR walk's fused updates run.

    ``{"gemm_q_rows", "csr_tiles", "csr_groups", "gemm_o_heads"} ->
    (live, launched)``, each summed over every leading axis of the plan
    (layers, batch, heads).  ``live`` is an int32 device scalar;
    ``launched`` is a static int read from the plan's shapes, which carry
    its geometry, except where it counts the CSR walk:

      * ``gemm_q_rows``: compact row blocks GEMM-Q computes (Σ ``row_cnt``)
        against its ``Cr`` row slots per sample;
      * ``csr_tiles``: (q-block, kv-block) tiles CSR attention computes
        (Σ ``kv_row_cnt`` over live rows) against the tiles it launches:
        ``B·S`` slots bucketed; the uniform kernel's walk, resident or
        windowed (see :func:`csr_path`), on one device or per shard,
        launches only the live tiles, so there ``launched`` is ``live``;
      * ``csr_groups``: the same live tiles against ``G`` times the
        online-softmax updates the walk runs, ``G`` the blocks one update
        fuses (``kernels.flashomni_attention.walk_group``): a row of ``n``
        live blocks takes ``ceil(n / G)`` updates.  The windowed walk
        restarts a group at each window cut, so this counts the resident
        walk's groups.  The bucketed grid updates one tile a slot, so
        there it equals ``csr_tiles``;
      * ``gemm_o_heads``: (row, head) slots GEMM-O reduces (Σ ``head_cnt``)
        against ``B·Cr·H`` uniform, ``B·S`` bucketed.

    Padding slots (rows past ``q_cnt`` / ``row_cnt``, columns past a row's
    count) take a grid step and are not live work.
    """
    from repro.kernels.flashomni_attention import walk_group
    total = lambda a: jnp.sum(a, dtype=jnp.int32)

    def walk(q_cnt, row_cnt, row_ids):
        live_rows = jnp.arange(row_cnt.shape[-1]) < q_cnt[..., None]
        cnt = jnp.where(live_rows, row_cnt, 0)
        tiles, g = total(cnt), walk_group(row_ids.shape[-1])
        return (tiles, tiles), (tiles, g * total(-(-cnt // g)))

    if plan.shd_kv_row_ids is not None:
        csr, groups = walk(plan.shd_q_cnt, plan.shd_kv_row_cnt,
                           plan.shd_kv_row_ids)
    elif plan.bkt_kv_cnt is not None:
        csr = groups = total(plan.bkt_kv_cnt), plan.bkt_kv_ids.size
    else:
        csr, groups = walk(plan.q_cnt, plan.kv_row_cnt, plan.kv_row_ids)
    if plan.gmo_head_cnt is not None:
        gmo = total(plan.gmo_head_cnt), plan.gmo_head_ids.size
    else:
        gmo = total(plan.head_cnt), plan.head_ids.size
    return {"gemm_q_rows": (total(plan.row_cnt), plan.row_ids.size),
            "csr_tiles": csr, "csr_groups": groups, "gemm_o_heads": gmo}


def empty_plan_like(batch: int, heads: int, n_tokens: int, cfg) -> DispatchPlan:
    """All-live plan matching the all-ones init symbols (warmup state)."""
    t = cfg.mask.n_blocks(n_tokens)
    m_c = jnp.ones((batch, heads, t), jnp.bool_)
    m_s = jnp.ones((batch, heads, t, t), jnp.bool_)
    return build_dispatch_plan(m_c, m_s, cfg, n_tokens)

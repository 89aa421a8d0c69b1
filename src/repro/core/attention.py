"""FlashOmni attention — XLA structural-sparse path (DESIGN §2).

Two implementations of the same semantics live in this repo:

  * :mod:`repro.kernels.flashomni_attention` — the Pallas TPU kernel with
    per-(i,j) CSR skipping (the paper's Algorithm 1, adapted to the TPU
    sequential grid).  Used on real TPU hardware.
  * this module — a pjit/XLA path with **structural** sparsity that the
    multi-pod dry-run lowers.  Compute for cached Q blocks is removed by a
    capacity-padded gather on the spatial axis (feature caching, ``S_c``),
    and the KV reduction runs over the capacity-padded **union** of KV
    blocks needed by any live row (``S_s``), with the exact per-(i,j) mask
    applied inside the gathered subset.  FLOPs in the compiled HLO shrink
    with both sparsity ratios, so the roofline analysis sees the win.
    When ``cap_kv`` can truncate the union (``cap_kv < T_kv``) the
    reduction switches to the PER-ROW CSR layout (each live row gathers
    its own KV-block list) so truncation semantics match the Pallas
    kernel exactly — same FLOPs, one extra gather dimension.

Masks follow the repo convention: boolean, True = compute.
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core.symbols import active_indices, clamp_mask_topk

__all__ = [
    "SparseAttentionSpec",
    "dense_attention",
    "masked_block_attention",
    "attention_plan_indices",
    "sparse_attention_from_plan",
    "sparse_attention_xla",
    "sparse_decode_attention",
]

_NEG_INF = -1e30


class SparseAttentionSpec(NamedTuple):
    """Static capacities for the structural path (part of the jit signature)."""

    block_q: int
    block_kv: int
    cap_q: int       # max live Q blocks per (batch, head)
    cap_kv: int      # max live KV blocks in the per-head union
    kv_buckets: int = 1  # occupancy buckets in the Pallas CSR grid (plan.py)


def dense_attention(q, k, v, *, scale: Optional[float] = None, mask=None):
    """Plain softmax attention oracle.  q,k,v: (..., N, d)."""
    scale = (q.shape[-1] ** -0.5) if scale is None else scale
    s = jnp.einsum("...qd,...kd->...qk", q, k).astype(jnp.float32) * scale
    if mask is not None:
        s = jnp.where(mask, s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("...qk,...kd->...qd", p, v.astype(jnp.float32)).astype(q.dtype)


def _block_mask_to_tokens(m_s: jax.Array, block_q: int, block_kv: int, n_q: int, n_kv: int):
    """(…, T_q, T_kv) block mask -> (…, n_q, n_kv) token mask."""
    m = jnp.repeat(jnp.repeat(m_s, block_q, axis=-2), block_kv, axis=-1)
    return m[..., :n_q, :n_kv]


def masked_block_attention(q, k, v, m_c, m_s, o_reuse, *, block_q, block_kv,
                           scale: Optional[float] = None):
    """Dense oracle with FlashOmni semantics (used by tests/ref):

    rows in blocks with ``m_c == 0`` take ``o_reuse``; live rows attend only
    to KV blocks with ``m_s == 1``.
    """
    n_q, n_kv = q.shape[-2], k.shape[-2]
    tok_mask = _block_mask_to_tokens(m_s, block_q, block_kv, n_q, n_kv)
    out = dense_attention(q, k, v, scale=scale, mask=tok_mask)
    row_live = jnp.repeat(m_c, block_q, axis=-1)[..., :n_q]
    return jnp.where(row_live[..., None], out, o_reuse)


def _gather_blocks(x_blocks: jax.Array, ids: jax.Array) -> jax.Array:
    """Gather block rows: x_blocks (..., T, b, d), ids (..., C) -> (..., C, b, d).

    The index keeps size-1 trailing dims, so each id copies a whole
    (b, d) block.  Broadcasting it to (..., C, b, d) would instead gather
    element by element through an int32 index as large as the output,
    which on a TPU is orders of magnitude slower."""
    return jnp.take_along_axis(x_blocks, ids[..., None, None], axis=-3)


def _gather_row_blocks(x_blocks: jax.Array, ids: jax.Array) -> jax.Array:
    """Per-row block gather: x_blocks (..., T, b, d), ids (..., C, Ck) ->
    (..., C, Ck, b, d) — each row gets its own KV-block list (CSR layout)."""
    flat = _gather_blocks(x_blocks, ids.reshape(*ids.shape[:-2], -1))
    return flat.reshape(*ids.shape, *x_blocks.shape[-2:])


def scatter_blocks(base: jax.Array, ids: jax.Array, cnt: jax.Array,
                   vals: jax.Array) -> jax.Array:
    """Scatter capacity-padded block rows into ``base`` (..., T, b, d).

    Padding slots (slot >= cnt) are masked out, so they can never clobber a
    live block that shares their (duplicated) id.

    §Perf iteration C3: implemented as a ONE-HOT EINSUM rather than an HLO
    scatter — data-dependent scatters on a sequence-sharded axis forced
    GSPMD to all-gather the whole operand (188 GB/step on the 33K HunyuanVideo
    cell); the einsum contracts the capacity axis instead, keeps the token
    axis sharded, and runs on the MXU (~3 TFLOP extra vs 3.8 s of ICI).
    Duplicate padded ids are benign: their mask row is zero.
    """
    t = base.shape[-3]
    slot = jnp.arange(ids.shape[-1], dtype=jnp.int32)
    live = slot < cnt[..., None]                              # (..., C)
    onehot = jax.nn.one_hot(jnp.where(live, ids, t), t + 1,
                            dtype=base.dtype)[..., :t]        # (..., C, T)
    scattered = jnp.einsum("...ct,...cbd->...tbd", onehot,
                           vals.astype(base.dtype))
    written = jnp.einsum("...ct->...t", onehot)               # 0/1 per block
    return jnp.where(written[..., None, None] > 0, scattered, base)


def attention_plan_indices(m_c: jax.Array, m_s: jax.Array,
                           spec: SparseAttentionSpec):
    """Index-decode step of the structural path (runs at Update time only).

    Returns ``(q_ids, q_cnt, kv_ids, kv_cnt, pair_live)`` — the attention
    slice of a :class:`repro.core.plan.DispatchPlan`.  All sort/top-k work
    of the XLA path lives here.
    """
    q_ids, q_cnt = active_indices(m_c, spec.cap_q)                     # (..., Cq)
    # KV-block union over live rows, importance = how many live rows need
    # the block; clamped to the static capacity.  The union layout is only
    # consumed when cap_kv admits the full union (cap_kv == T_kv, so the
    # clamp is a no-op); whenever truncation is possible the reduction
    # runs over the per-row CSR lists instead (shared Pallas semantics).
    need = jnp.sum(m_s & m_c[..., None], axis=-2)                      # (..., T_kv)
    kv_union = clamp_mask_topk(need > 0, need, spec.cap_kv)
    kv_ids, kv_cnt = active_indices(kv_union, spec.cap_kv)             # (..., Ck)
    pair = jnp.take_along_axis(
        jnp.take_along_axis(m_s, q_ids[..., :, None], axis=-2),
        kv_ids[..., None, :], axis=-1,
    )                                                                   # (..., Cq, Ck)
    kv_valid = jnp.arange(spec.cap_kv) < kv_cnt[..., None]             # (..., Ck)
    pair_live = pair & kv_valid[..., None, :]
    return q_ids, q_cnt, kv_ids, kv_cnt, pair_live


def sparse_attention_from_plan(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    o_reuse: jax.Array,
    q_ids: jax.Array,
    q_cnt: jax.Array,
    kv_ids: jax.Array,
    kv_cnt: jax.Array,
    pair_live: jax.Array,
    spec: SparseAttentionSpec,
    *,
    scale: Optional[float] = None,
    q_chunk_blocks: int = 16,
    q_src_ids: Optional[jax.Array] = None,
    kv_row_ids: Optional[jax.Array] = None,
    kv_row_cnt: Optional[jax.Array] = None,
    force_per_row: bool = False,
) -> jax.Array:
    """Structurally sparse attention over PRECOMPUTED indices.

    Shapes: q,k,v,o_reuse (..., N, d); index arrays as returned by
    :func:`attention_plan_indices`.  Contains no index decoding — a
    Dispatch step traces only gathers/einsums/softmax from here.

    ``q_src_ids`` optionally re-maps the Q gather to a different (compact)
    block layout while the output scatter keeps the full-layout ``q_ids``
    (GEMM-Q layout fusion).  The gathered live Q blocks are processed in
    chunks of ``q_chunk_blocks`` so peak score memory is
    O(chunk·bq·Ckv·bk) regardless of N (needed for the 33K-token
    HunyuanVideo cells).

    ``kv_row_ids``/``kv_row_cnt`` (the DispatchPlan's per-live-row CSR
    column lists) switch the reduction to the PER-ROW layout whenever
    ``cap_kv`` can truncate the per-head KV union (``cap_kv < T_kv``):
    each live row gathers its own KV-block list, which is exactly the
    Pallas CSR kernel's semantics.  The old union layout dropped whole
    columns globally per head when the union overflowed the capacity —
    the documented XLA-vs-Pallas divergence this path closes.  With
    capacity admitting the full union both layouts are bit-identical and
    the cheaper union gather is used.
    """
    bq, bk = spec.block_q, spec.block_kv
    d = q.shape[-1]
    n_kv = k.shape[-2]
    t_q = o_reuse.shape[-2] // bq
    t_kv = n_kv // bk
    scale = (d ** -0.5) if scale is None else scale
    q_src_ids = q_ids if q_src_ids is None else q_src_ids
    # Per-row layout whenever truncation is possible: cap_kv below the full
    # union, OR occupancy buckets (a narrow bucket can truncate a row even
    # with cap_kv == T_kv; the bucket-truncated counts live in kv_row_cnt),
    # OR the caller forces it (mesh-folded plans: the pair clamp lives in
    # kv_row_cnt, which the union layout would ignore).
    per_row = kv_row_ids is not None and (force_per_row
                                          or spec.cap_kv < t_kv
                                          or spec.kv_buckets > 1)

    qb = q.reshape(*q.shape[:-2], q.shape[-2] // bq, bq, d)
    kb = k.reshape(*k.shape[:-2], t_kv, bk, d)
    vb = v.reshape(*v.shape[:-2], t_kv, bk, d)
    if not per_row:
        kg = _gather_blocks(kb, kv_ids)                                # (..., Ck, bk, d)
        vg = _gather_blocks(vb, kv_ids)

    def q_chunk(ids_c, live_c):
        """One chunk of live q-block ids + its pair mask -> outputs."""
        qg = _gather_blocks(qb, ids_c)                                 # (..., cc, bq, d)
        s = jnp.einsum("...ipd,...jqd->...ipjq", qg, kg).astype(jnp.float32) * scale
        s = jnp.where(live_c[..., :, None, :, None], s, _NEG_INF)
        cc = ids_c.shape[-1]
        sf = s.reshape(*s.shape[:-4], cc, bq, spec.cap_kv * bk)
        p = jax.nn.softmax(sf, axis=-1).reshape(s.shape)
        return jnp.einsum("...ipjq,...jqd->...ipd", p,
                          vg.astype(jnp.float32)).astype(q.dtype)

    def q_chunk_rowcsr(ids_c, rids_c, rcnt_c):
        """One chunk of live q blocks, each with its OWN KV-block list."""
        qg = _gather_blocks(qb, ids_c)                                 # (..., cc, bq, d)
        kg_r = _gather_row_blocks(kb, rids_c)                          # (..., cc, Ck, bk, d)
        vg_r = _gather_row_blocks(vb, rids_c)
        s = jnp.einsum("...ipd,...ijqd->...ipjq", qg,
                       kg_r).astype(jnp.float32) * scale
        live = jnp.arange(rids_c.shape[-1]) < rcnt_c[..., None]        # (..., cc, Ck)
        s = jnp.where(live[..., :, None, :, None], s, _NEG_INF)
        cc = ids_c.shape[-1]
        sf = s.reshape(*s.shape[:-4], cc, bq, spec.cap_kv * bk)
        p = jax.nn.softmax(sf, axis=-1).reshape(s.shape)
        return jnp.einsum("...ipjq,...ijqd->...ipd", p,
                          vg_r.astype(jnp.float32)).astype(q.dtype)

    if spec.cap_q <= q_chunk_blocks or spec.cap_q % q_chunk_blocks != 0:
        og = (q_chunk_rowcsr(q_src_ids, kv_row_ids, kv_row_cnt) if per_row
              else q_chunk(q_src_ids, pair_live))
    elif per_row:
        n_ch = spec.cap_q // q_chunk_blocks
        ids_ch = jnp.moveaxis(
            q_src_ids.reshape(*q_src_ids.shape[:-1], n_ch, q_chunk_blocks), -2, 0)
        rids_ch = jnp.moveaxis(
            kv_row_ids.reshape(*kv_row_ids.shape[:-2], n_ch, q_chunk_blocks,
                               kv_row_ids.shape[-1]), -3, 0)
        rcnt_ch = jnp.moveaxis(
            kv_row_cnt.reshape(*kv_row_cnt.shape[:-1], n_ch, q_chunk_blocks),
            -2, 0)
        og_ch = jax.lax.map(lambda t: q_chunk_rowcsr(*t),
                            (ids_ch, rids_ch, rcnt_ch))
        og = jnp.moveaxis(og_ch, 0, -4)
        og = og.reshape(*og.shape[:-4], spec.cap_q, bq, d)
    else:
        n_ch = spec.cap_q // q_chunk_blocks
        ids_ch = jnp.moveaxis(
            q_src_ids.reshape(*q_src_ids.shape[:-1], n_ch, q_chunk_blocks), -2, 0)
        live_ch = jnp.moveaxis(
            pair_live.reshape(*pair_live.shape[:-2], n_ch, q_chunk_blocks,
                              pair_live.shape[-1]), -3, 0)
        og_ch = jax.lax.map(lambda t: q_chunk(*t), (ids_ch, live_ch))
        og = jnp.moveaxis(og_ch, 0, -4)                                # (..., n_ch, cc, bq, d)
        og = og.reshape(*og.shape[:-4], spec.cap_q, bq, d)

    # Scatter computed blocks over the reuse baseline (padding slots dropped).
    out_blocks = o_reuse.reshape(*o_reuse.shape[:-2], t_q, bq, d)
    out_blocks = scatter_blocks(out_blocks, q_ids, q_cnt, og)
    return out_blocks.reshape(o_reuse.shape)


def sparse_attention_xla(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    m_c: jax.Array,
    m_s: jax.Array,
    o_reuse: jax.Array,
    spec: SparseAttentionSpec,
    *,
    scale: Optional[float] = None,
    q_chunk_blocks: int = 16,
) -> jax.Array:
    """Structurally sparse attention (see module docstring).

    Shapes: q,k,v,o_reuse (..., N, d); m_c (..., T_q); m_s (..., T_q, T_kv).
    Mask-level entry point: decodes indices per call (legacy rebuild path).
    The Update–Dispatch engine instead decodes once via
    :func:`attention_plan_indices` and calls
    :func:`sparse_attention_from_plan` on every Dispatch step.  When
    ``cap_kv`` can truncate the union the per-row CSR lists are decoded
    too, so this path shares the Pallas per-row truncation semantics.
    """
    q_ids, q_cnt, kv_ids, kv_cnt, pair_live = attention_plan_indices(
        m_c, m_s, spec)
    kv_row_ids = kv_row_cnt = None
    if spec.cap_kv < m_s.shape[-1] or spec.kv_buckets > 1:
        rows = jnp.take_along_axis(m_s, q_ids[..., :, None], axis=-2)
        kv_row_ids, kv_row_cnt = active_indices(rows, spec.cap_kv)
    return sparse_attention_from_plan(
        q, k, v, o_reuse, q_ids, q_cnt, kv_ids, kv_cnt, pair_live, spec,
        scale=scale, q_chunk_blocks=q_chunk_blocks,
        kv_row_ids=kv_row_ids, kv_row_cnt=kv_row_cnt)


def sparse_decode_attention(
    q: jax.Array,
    k_cache: jax.Array,
    v_cache: jax.Array,
    kv_ids: jax.Array,
    kv_cnt: jax.Array,
    block_kv: int,
    *,
    scale: Optional[float] = None,
    positions: Optional[jax.Array] = None,
    cache_len: Optional[jax.Array] = None,
) -> jax.Array:
    """Block-sparse decode: one (or few) query tokens against a gathered
    subset of KV-cache blocks (LM serving adaptation of ``S_s``).

    q: (..., n_new, d); caches: (..., S, d); kv_ids/kv_cnt from
    :func:`active_indices` over the per-head KV keep mask.
    """
    d = q.shape[-1]
    s_total = k_cache.shape[-2]
    t_kv = s_total // block_kv
    scale = (d ** -0.5) if scale is None else scale
    kb = k_cache.reshape(*k_cache.shape[:-2], t_kv, block_kv, d)
    vb = v_cache.reshape(*v_cache.shape[:-2], t_kv, block_kv, d)
    kg = _gather_blocks(kb, kv_ids)
    vg = _gather_blocks(vb, kv_ids)
    s = jnp.einsum("...nd,...jqd->...njq", q, kg).astype(jnp.float32) * scale
    valid = jnp.arange(kv_ids.shape[-1]) < kv_cnt[..., None]            # (..., Ck)
    live = valid[..., None, :, None]
    if cache_len is not None:
        tok_pos = kv_ids[..., :, None] * block_kv + jnp.arange(block_kv)
        live = live & (tok_pos < cache_len[..., None, None, None])
    s = jnp.where(live, s, _NEG_INF)
    sf = s.reshape(*s.shape[:-2], -1)
    p = jax.nn.softmax(sf, axis=-1).reshape(s.shape)
    return jnp.einsum("...njq,...jqd->...nd", p, vg.astype(jnp.float32)).astype(q.dtype)

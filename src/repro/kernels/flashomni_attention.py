"""FlashOmni general sparse attention — Pallas TPU kernels (paper §3.4).

Two variants of the paper's Algorithm 1, adapted to the TPU execution model
(DESIGN §2):

``flashomni_attention_csr``  (default, TPU-native structural skipping)
    The grid covers only LIVE work: ``(BH, Cq, Ckv)`` where ``Cq`` is the
    static capacity of live Q blocks and the KV reduction runs over
    per-row CSR column lists.  Scalar-prefetched index arrays drive the
    BlockSpec index maps, so skipped tiles are never DMA'd and never
    occupy a grid slot — this is what preserves the paper's ~1:1
    speedup:sparsity on a sequential-grid machine.  Cached rows are left
    untouched via input/output aliasing of the ``o_reuse`` tensor (their
    forecast value is produced by the ``taylor_reuse`` element-wise kernel,
    the paper's "alternatively, an elementwise kernel can be invoked").

``flashomni_attention_csr_bucketed``  (occupancy-bucketed two-level grid)
    The uniform CSR grid still pads every live row's reduction to the
    static ``cap_kv`` — mostly-idle slots on the strongly bimodal plans
    the deployment strategies emit (``hunyuan-1.5x`` sliding-window heads
    have tiny per-row KV counts).  The bucketed variant runs a TWO-LEVEL
    grid (bucket × row × per-bucket Ckv, flattened to ``(B, S)`` with
    ``S = Σ rows_b · width_b``): at plan-build time the ``H·Cq`` layout
    rows are sorted by KV occupancy into a static set of halving-width
    buckets (:func:`repro.core.plan.bucket_geometry`), so a row with 3
    live KV blocks occupies a ≈3-wide reduction instead of a
    ``cap_kv``-wide one.  The per-slot (row, j, offset, last) decode is a
    compile-time constant of the geometry, scalar-prefetched like the
    index lists; the uniform kernel is exactly the ``n_buckets = 1``
    degenerate case of this layout.  Bucket truncation is folded back
    into ``kv_row_cnt`` at plan build, so bucketed and uniform outputs
    are BIT-IDENTICAL (same ascending-id flash accumulation order).

``flashomni_attention_symbols``  (paper-faithful predication)
    The grid covers every ``(i, j)`` tile; each program decodes the packed
    uint8 symbols with the paper's bitwise ``F``/``J`` and predicates
    compute with ``@pl.when`` — including the fused cache-then-reuse copy
    branch (Algorithm 1 lines 5–10).  Demonstrates symbol-decode fidelity;
    DMA traffic is NOT reduced (documented GPU→TPU non-transfer).

All validate against :func:`repro.kernels.ref.attention_ref` in
``interpret=True`` mode; on real v5e the CSR variants are the serving path.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


__all__ = [
    "flashomni_attention_csr",
    "flashomni_attention_csr_bucketed",
    "flashomni_attention_symbols",
]

_NEG_INF = -1e30
_LANES = 128  # TPU vreg lane count: m/l scratch kept (bq, 128)-shaped.


# ---------------------------------------------------------------------------
# CSR variant
# ---------------------------------------------------------------------------

def _csr_kernel(
    # scalar prefetch
    q_ids_ref, q_src_ids_ref, kv_ids_ref, kv_cnt_ref,
    # inputs
    q_ref, k_ref, v_ref, o_reuse_ref,   # o_reuse aliased to output (untouched)
    # outputs
    o_ref,
    # scratch
    acc_ref, m_ref, l_ref,
    *,
    scale: float,
    ckv: int,
):
    c, j = pl.program_id(1), pl.program_id(2)
    bh = pl.program_id(0)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when(j < kv_cnt_ref[bh, c])
    def _compute():
        q = q_ref[0].astype(jnp.float32)                    # (bq, d)
        k = k_ref[0].astype(jnp.float32)                    # (bk, d)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        m_prev = m_ref[:, :1]                               # (bq, 1)
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)                     # (bq, 1)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        v = v_ref[0].astype(jnp.float32)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot(
            p, v, preferred_element_type=jnp.float32)

    @pl.when(j == ckv - 1)
    def _finalize():
        l = l_ref[:, :1]
        l = jnp.where(l == 0.0, 1.0, l)                     # fully-skipped row guard
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


def flashomni_attention_csr(
    q: jax.Array,             # (BH, N_q, d) — full OR compact (layout fusion)
    k: jax.Array,             # (BH, N_kv, d)
    v: jax.Array,             # (BH, N_kv, d)
    o_reuse: jax.Array,       # (BH, N, d) — cached/forecast baseline (aliased)
    q_ids: jax.Array,         # (BH, Cq) int32 live q-block ids (output layout)
    kv_ids: jax.Array,        # (BH, Cq, Ckv) int32 per-row live kv-block ids
    kv_cnt: jax.Array,        # (BH, Cq) int32
    *,
    block_q: int,
    block_kv: int,
    scale: Optional[float] = None,
    interpret: bool = False,
    q_src_ids: Optional[jax.Array] = None,  # (BH, Cq) q-block ids in Q's layout
) -> jax.Array:
    """CSR sparse attention.  ``q_src_ids`` decouples where live Q blocks
    are READ from where outputs are WRITTEN: pass the compact-slot ids of a
    GEMM-Q ``(Cr·bm, F)`` output to chain the two kernels without a scatter
    (the compact-layout fusion GEMM-Q was designed for).  Defaults to
    ``q_ids`` (full-layout Q)."""
    bhs, n_q, d = q.shape
    n_kv = k.shape[1]
    assert n_q % block_q == 0 and n_kv % block_kv == 0
    assert o_reuse.shape[1] % block_q == 0
    cq, ckv = q_ids.shape[1], kv_ids.shape[2]
    scale = (d ** -0.5) if scale is None else scale
    q_src_ids = q_ids if q_src_ids is None else q_src_ids

    grid = (bhs, cq, ckv)
    kernel = functools.partial(_csr_kernel, scale=scale, ckv=ckv)
    flat_kv = kv_ids.reshape(bhs, cq * ckv)

    def q_map(bh, c, j, q_ids_ref, q_src_ids_ref, kv_ids_ref, kv_cnt_ref):
        return (bh, q_src_ids_ref[bh, c], 0)

    def kv_map(bh, c, j, q_ids_ref, q_src_ids_ref, kv_ids_ref, kv_cnt_ref):
        # Clamp padded slots to the last live column (re-DMA of a resident
        # block — Mosaic elides the copy when the index is unchanged).
        jj = jnp.maximum(jnp.minimum(j, kv_cnt_ref[bh, c] - 1), 0)
        return (bh, kv_ids_ref[bh, c * ckv + jj], 0)

    def o_map(bh, c, j, q_ids_ref, q_src_ids_ref, kv_ids_ref, kv_cnt_ref):
        return (bh, q_ids_ref[bh, c], 0)

    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, block_q, d), q_map),
                pl.BlockSpec((1, block_kv, d), kv_map),
                pl.BlockSpec((1, block_kv, d), kv_map),
                pl.BlockSpec((1, block_q, d), o_map),       # o_reuse (aliased)
            ],
            out_specs=pl.BlockSpec((1, block_q, d), o_map),
            scratch_shapes=[
                pltpu.VMEM((block_q, d), jnp.float32),
                pltpu.VMEM((block_q, _LANES), jnp.float32),
                pltpu.VMEM((block_q, _LANES), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct(o_reuse.shape, o_reuse.dtype),
        # NB: alias indices count the scalar-prefetch operands too.
        input_output_aliases={7: 0},                        # o_reuse -> out
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
        ),
        interpret=interpret,
        name="flashomni_csr_attention",
    )(q_ids, q_src_ids, flat_kv, kv_cnt, q, k, v, o_reuse)


# ---------------------------------------------------------------------------
# Occupancy-bucketed CSR variant — two-level (bucket × row × Ckv) grid
# ---------------------------------------------------------------------------

def _csr_bucketed_kernel(
    # scalar prefetch: static slot decode + plan layout
    srow_ref, jof_ref, soff_ref, slast_ref,
    head_ref, q_write_ref, q_read_ref, kv_ids_ref, kv_cnt_ref,
    # inputs
    q_ref, k_ref, v_ref, o_reuse_ref,   # o_reuse aliased to output (untouched)
    # outputs
    o_ref,
    # scratch
    acc_ref, m_ref, l_ref,
    *,
    scale: float,
):
    b, s = pl.program_id(0), pl.program_id(1)
    r = srow_ref[s]
    jof = jof_ref[s]

    @pl.when(jof == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when(jof < kv_cnt_ref[b, r])
    def _compute():
        q = q_ref[0].astype(jnp.float32)                    # (bq, d)
        k = k_ref[0].astype(jnp.float32)                    # (bk, d)
        s_ = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32) * scale
        m_prev = m_ref[:, :1]                               # (bq, 1)
        m_cur = jnp.max(s_, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s_ - m_new)
        alpha = jnp.exp(m_prev - m_new)                     # (bq, 1)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        v = v_ref[0].astype(jnp.float32)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot(
            p, v, preferred_element_type=jnp.float32)

    @pl.when(slast_ref[s] == 1)
    def _finalize():
        l = l_ref[:, :1]
        l = jnp.where(l == 0.0, 1.0, l)                     # fully-skipped row guard
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


def flashomni_attention_csr_bucketed(
    q: jax.Array,             # (B·H, N_q, d) — full OR compact (layout fusion)
    k: jax.Array,             # (B·H, N_kv, d)
    v: jax.Array,             # (B·H, N_kv, d)
    o_reuse: jax.Array,       # (B·H, N, d) — cached/forecast baseline (aliased)
    bkt_head: jax.Array,      # (B, R) int32 head of each layout row
    bkt_q_write: jax.Array,   # (B, R) int32 output q-block id (dead rows → T_q)
    bkt_q_read: jax.Array,    # (B, R) int32 q-block id in Q's layout (dead → 0)
    bkt_kv_ids: jax.Array,    # (B, S) int32 per-slot kv-block id
    bkt_kv_cnt: jax.Array,    # (B, R) int32 bucket-truncated live KV count
    geometry,                 # ((rows, width), ...) — bucket_geometry output
    *,
    heads: int,
    block_q: int,
    block_kv: int,
    scale: Optional[float] = None,
    interpret: bool = False,
) -> jax.Array:
    """Occupancy-bucketed CSR sparse attention (see module docstring).

    Grid is ``(B, S)`` with ``S = Σ rows_b·width_b`` — the two-level
    bucket × row × per-bucket-Ckv structure flattened so consecutive grid
    steps walk one row's reduction start-to-finish.  The head axis is
    folded into the layout rows (``bh = b·heads + bkt_head[b, r]`` in
    every index map), which is what lets a sliding-window head's short
    rows share narrow buckets while a full head's rows take wide ones.
    Dead layout rows write zeros to a one-block trash pad appended past
    ``N``; live-but-empty rows (zero live KV blocks) write zeros exactly
    like the uniform kernel's fully-skipped-row guard.
    """
    from repro.core.plan import bucket_slot_layout

    bhs, n_q, d = q.shape
    n_kv = k.shape[1]
    n_out = o_reuse.shape[1]
    assert bhs % heads == 0
    assert n_q % block_q == 0 and n_kv % block_kv == 0 and n_out % block_q == 0
    batch = bhs // heads
    srow, jof, soff, slast = bucket_slot_layout(geometry)
    s_total = int(srow.shape[0])
    scale = (d ** -0.5) if scale is None else scale
    kernel = functools.partial(_csr_bucketed_kernel, scale=scale)

    # One trash block per (b, h) past the real tokens: dead layout rows
    # land there (q_write == T_q); sliced off after the call.
    o_pad = jnp.concatenate(
        [o_reuse, jnp.zeros((bhs, block_q, d), o_reuse.dtype)], axis=1)

    def q_map(b, s, srow_r, jof_r, soff_r, slast_r, head_r, qw_r, qr_r,
              kvi_r, kvc_r):
        r = srow_r[s]
        return (b * heads + head_r[b, r], qr_r[b, r], 0)

    def kv_map(b, s, srow_r, jof_r, soff_r, slast_r, head_r, qw_r, qr_r,
               kvi_r, kvc_r):
        r = srow_r[s]
        # Clamp padded slots to the last live column (re-DMA of a resident
        # block — Mosaic elides the copy when the index is unchanged).
        jj = jnp.maximum(jnp.minimum(jof_r[s], kvc_r[b, r] - 1), 0)
        return (b * heads + head_r[b, r], kvi_r[b, soff_r[s] + jj], 0)

    def o_map(b, s, srow_r, jof_r, soff_r, slast_r, head_r, qw_r, qr_r,
              kvi_r, kvc_r):
        r = srow_r[s]
        return (b * heads + head_r[b, r], qw_r[b, r], 0)

    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=9,
            grid=(batch, s_total),
            in_specs=[
                pl.BlockSpec((1, block_q, d), q_map),
                pl.BlockSpec((1, block_kv, d), kv_map),
                pl.BlockSpec((1, block_kv, d), kv_map),
                pl.BlockSpec((1, block_q, d), o_map),       # o_reuse (aliased)
            ],
            out_specs=pl.BlockSpec((1, block_q, d), o_map),
            scratch_shapes=[
                pltpu.VMEM((block_q, d), jnp.float32),
                pltpu.VMEM((block_q, _LANES), jnp.float32),
                pltpu.VMEM((block_q, _LANES), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct(o_pad.shape, o_pad.dtype),
        # NB: alias indices count the scalar-prefetch operands too.
        input_output_aliases={12: 0},                       # o_pad -> out
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="flashomni_csr_attention_bucketed",
    )(jnp.asarray(srow), jnp.asarray(jof), jnp.asarray(soff),
      jnp.asarray(slast), bkt_head, bkt_q_write, bkt_q_read,
      bkt_kv_ids, bkt_kv_cnt, q, k, v, o_pad)
    return out[:, :n_out]


# ---------------------------------------------------------------------------
# Symbols (predication) variant — paper Algorithm 1 verbatim
# ---------------------------------------------------------------------------

def _sym_kernel(
    # scalar prefetch
    s_c_ref, s_s_ref,
    # inputs
    q_ref, k_ref, v_ref, o_reuse_ref,
    # outputs
    o_ref,
    # scratch
    acc_ref, m_ref, l_ref,
    *,
    scale: float,
    t_kv: int,
):
    bh, i, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    # F(S_c, i): spatial-axis decode (bitwise, big-endian).
    byte_c = s_c_ref[bh, i // 8].astype(jnp.int32)
    f_live = (byte_c >> (7 - i % 8)) & 1
    # J(S_s, i, j): reduction-axis decode on the row-major flattened matrix.
    flat = i * t_kv + j
    byte_s = s_s_ref[bh, flat // 8].astype(jnp.int32)
    j_live = (byte_s >> (7 - flat % 8)) & 1

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # Cache-then-Reuse (Algorithm 1 lines 5-10): fused element-wise copy of
    # the forecast feature, then the CTA-equivalent returns.
    @pl.when((f_live == 0) & (j == t_kv - 1))
    def _reuse():
        o_ref[0] = o_reuse_ref[0]

    # Compute-on-Demand (lines 11-19) with reduction-axis skipping (line 13).
    @pl.when((f_live == 1) & (j_live == 1))
    def _compute():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        m_prev = m_ref[:, :1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot(
            p, v_ref[0].astype(jnp.float32), preferred_element_type=jnp.float32)

    @pl.when((f_live == 1) & (j == t_kv - 1))
    def _finalize():
        l = l_ref[:, :1]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


def flashomni_attention_symbols(
    q: jax.Array,             # (BH, N, d)
    k: jax.Array,
    v: jax.Array,
    o_reuse: jax.Array,       # (BH, N, d) forecast features (OP_reuse output)
    s_c: jax.Array,           # (BH, cbytes) uint8 packed caching symbol
    s_s: jax.Array,           # (BH, fbytes) uint8 packed skipping symbol
    *,
    block_q: int,
    block_kv: int,
    scale: Optional[float] = None,
    interpret: bool = False,
) -> jax.Array:
    bhs, n, d = q.shape
    n_kv = k.shape[1]
    assert n % block_q == 0 and n_kv % block_kv == 0
    t_q, t_kv = n // block_q, n_kv // block_kv
    scale = (d ** -0.5) if scale is None else scale
    kernel = functools.partial(_sym_kernel, scale=scale, t_kv=t_kv)

    def qo_map(bh, i, j, s_c_ref, s_s_ref):
        return (bh, i, 0)

    def kv_map(bh, i, j, s_c_ref, s_s_ref):
        return (bh, j, 0)

    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(bhs, t_q, t_kv),
            in_specs=[
                pl.BlockSpec((1, block_q, d), qo_map),
                pl.BlockSpec((1, block_kv, d), kv_map),
                pl.BlockSpec((1, block_kv, d), kv_map),
                pl.BlockSpec((1, block_q, d), qo_map),
            ],
            out_specs=pl.BlockSpec((1, block_q, d), qo_map),
            scratch_shapes=[
                pltpu.VMEM((block_q, d), jnp.float32),
                pltpu.VMEM((block_q, _LANES), jnp.float32),
                pltpu.VMEM((block_q, _LANES), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct(o_reuse.shape, o_reuse.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
        ),
        interpret=interpret,
        name="flashomni_attention_symbols",
    )(s_c, s_s, q, k, v, o_reuse)

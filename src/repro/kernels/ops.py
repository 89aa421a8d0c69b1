"""Jit'd wrappers for the FlashOmni Pallas kernels.

These translate the engine's logical masks into the scalar-prefetch index
lists the kernels consume, pick interpret mode automatically off-TPU, and
guard the degenerate all-cached case (paper A.1.1 ``S_q`` degradation) where
the kernels would have no live work.

Tile shapes for the sparse GEMMs come from the calibration table in
:mod:`repro.kernels.tuning` (``kernel_tiles``), keyed per kernel kind and
reduction-width class — ``benchmarks/autotune.py`` populates it on real
TPUs; the checked-in default reproduces the hand-picked 512s.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.symbols import active_indices
from repro.kernels.flashomni_attention import (
    flashomni_attention_csr,
    flashomni_attention_symbols,
)
from repro.kernels.gemm_o import (gemm_o_sparse_bucketed_kernel,
                                  gemm_o_sparse_kernel)
from repro.kernels.gemm_q import gemm_q_sparse_kernel
from repro.kernels.taylor_reuse import taylor_reuse_kernel
from repro.kernels.tuning import kernel_tiles

__all__ = [
    "on_tpu",
    "flashomni_attention",
    "gemm_q",
    "gemm_o",
    "taylor_reuse",
    "scatter_rows",
]


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def scatter_rows(compact: jax.Array, row_ids: jax.Array, row_cnt: jax.Array,
                 base: jax.Array, block: int) -> jax.Array:
    """Scatter a compact (Cr·block, F) result back into ``base`` (N, F)."""
    cr = row_ids.shape[0]
    t = base.shape[0] // block
    vals = compact.reshape(cr, block, -1)
    slot = jnp.arange(cr, dtype=jnp.int32)
    sid = jnp.where(slot < row_cnt, row_ids, t)
    padded = jnp.concatenate(
        [base.reshape(t, block, -1), jnp.zeros((1, block, base.shape[-1]), base.dtype)], 0)
    padded = padded.at[sid].set(vals.astype(base.dtype))
    return padded[:t].reshape(base.shape)


@functools.partial(jax.jit, static_argnames=("block_q", "block_kv", "variant",
                                             "cap_q", "cap_kv", "interpret",
                                             "kv_buckets", "heads"))
def flashomni_attention(
    q: jax.Array,            # (BH, N, d)
    k: jax.Array,
    v: jax.Array,
    m_c: jax.Array,          # (BH, T_q) bool, True = compute
    m_s: jax.Array,          # (BH, T_q, T_kv) bool
    o_reuse: jax.Array,      # (BH, N, d)
    *,
    block_q: int,
    block_kv: int,
    variant: str = "csr",
    cap_q: Optional[int] = None,
    cap_kv: Optional[int] = None,
    interpret: Optional[bool] = None,
    kv_buckets: int = 1,
    heads: int = 1,
) -> jax.Array:
    """Unified sparse attention entry (kernel side of paper Fig. 4).

    ``kv_buckets > 1`` routes to the occupancy-bucketed two-level grid:
    the leading axis is interpreted as ``B·heads`` and the bucket layout
    folds the head axis (short sliding-window rows share narrow buckets
    across heads).  NB: buckets may TRUNCATE a row's KV list to its slot
    width — callers compare against a reference fed the same truncated
    counts (see ``tests/test_bucketed.py``).
    """
    interpret = (not on_tpu()) if interpret is None else interpret
    t_q, t_kv = m_c.shape[-1], m_s.shape[-1]
    if variant == "symbols":
        from repro.core.symbols import pack_bits
        s_c = pack_bits(m_c)
        s_s = pack_bits(m_s.reshape(m_s.shape[0], -1))
        return flashomni_attention_symbols(
            q, k, v, o_reuse, s_c, s_s,
            block_q=block_q, block_kv=block_kv, interpret=interpret)
    cap_q = t_q if cap_q is None else cap_q
    cap_kv = t_kv if cap_kv is None else cap_kv
    q_ids, q_cnt = active_indices(m_c, cap_q)
    rows = jnp.take_along_axis(m_s, q_ids[..., None], axis=-2)       # (BH, Cq, Tkv)
    kv_ids, kv_cnt = active_indices(rows, cap_kv)
    if kv_buckets > 1:
        from repro.core.plan import bucket_geometry, bucket_layout
        from repro.kernels.flashomni_attention import (
            flashomni_attention_csr_bucketed,
        )
        bh = m_c.shape[0]
        assert bh % heads == 0, (bh, heads)
        b = bh // heads
        geometry = bucket_geometry(cap_q, cap_kv, heads, kv_buckets)
        shp = lambda a: a.reshape(b, heads, *a.shape[1:])
        score = jnp.sum(rows, axis=-1).astype(jnp.float32)   # live-mass proxy
        bkt, _ = bucket_layout(
            shp(q_ids), shp(q_cnt), shp(q_ids), shp(kv_ids), shp(kv_cnt),
            shp(score), geometry, t_q)
        return flashomni_attention_csr_bucketed(
            q, k, v, o_reuse,
            bkt["bkt_head"], bkt["bkt_q_ids"], bkt["bkt_q_src"],
            bkt["bkt_kv_ids"], bkt["bkt_kv_cnt"], geometry,
            heads=heads, block_q=block_q, block_kv=block_kv,
            interpret=interpret)
    out = flashomni_attention_csr(
        q, k, v, o_reuse, q_ids, kv_ids, kv_cnt, q_cnt,
        block_q=block_q, block_kv=block_kv, interpret=interpret)
    # Degenerate all-cached guard: the kernel leaves the duplicated slot-0
    # block undefined when q_cnt == 0; select the pure-reuse tensor.
    any_live = (q_cnt > 0)[:, None, None]
    return jnp.where(any_live, out, o_reuse)


@functools.partial(jax.jit, static_argnames=("block_rows", "cap", "compact", "interpret"))
def gemm_q(
    x: jax.Array,            # (N, K)
    w: jax.Array,            # (K, F)
    row_mask: jax.Array,     # (T,) bool, T = N // block_rows
    *,
    block_rows: int,
    cap: Optional[int] = None,
    compact: bool = True,
    interpret: Optional[bool] = None,
):
    """GEMM-Q wrapper.  Returns ``(y, row_ids, row_cnt)``; ``y`` is compact
    (cap·block, F) when ``compact`` else scattered to (N, F) with zeros."""
    interpret = (not on_tpu()) if interpret is None else interpret
    t = row_mask.shape[-1]
    cap = t if cap is None else cap
    row_ids, row_cnt = active_indices(row_mask, cap)
    tiles = kernel_tiles("gemm_q", x.shape[-1])
    y = gemm_q_sparse_kernel(x, w, row_ids, block_rows=block_rows,
                             block_k=tiles.get("block_k", 512),
                             block_f=tiles.get("block_f", 512),
                             row_cnt=row_cnt, interpret=interpret)
    if not compact:
        base = jnp.zeros((x.shape[0], w.shape[-1]), x.dtype)
        y = scatter_rows(y, row_ids, row_cnt, base, block_rows)
    return y, row_ids, row_cnt


@functools.partial(jax.jit, static_argnames=("block_rows", "cap_rows", "cap_heads",
                                             "interpret", "hc_buckets"))
def gemm_o(
    o_heads: jax.Array,      # (H, N, dh)
    w: jax.Array,            # (H, dh, F)
    bias: jax.Array,         # (N, F) forecast OP_reuse(B_c)
    m_ch: jax.Array,         # (T, H) per-(row-block, head) live mask
    *,
    block_rows: int,
    cap_rows: Optional[int] = None,
    cap_heads: Optional[int] = None,
    interpret: Optional[bool] = None,
    hc_buckets: int = 1,
) -> jax.Array:
    """GEMM-O wrapper.  ``hc_buckets > 1`` routes to the occupancy-bucketed
    two-level grid over live-head counts (the GEMM-O analogue of the
    attention entry's ``kv_buckets``).  NB: buckets may TRUNCATE a row's
    head list to its slot width — callers compare against a reference fed
    the same truncated counts (see ``tests/test_bucketed_gemm.py``)."""
    interpret = (not on_tpu()) if interpret is None else interpret
    t, h = m_ch.shape
    cap_rows = t if cap_rows is None else cap_rows
    cap_heads = h if cap_heads is None else cap_heads
    live_rows = jnp.any(m_ch, axis=-1)
    row_ids, row_cnt = active_indices(live_rows, cap_rows)
    rows = jnp.take(m_ch, row_ids, axis=0)                           # (Cr, H)
    head_ids, head_cnt = active_indices(rows, cap_heads)
    # Padding slots duplicate the last live row; empty their head lists so
    # the bias-aliased kernel skips them (see _kernel's _done guard).
    head_cnt = jnp.where(jnp.arange(cap_rows) < row_cnt, head_cnt, 0)
    tiles = kernel_tiles("gemm_o", h)
    block_f = tiles.get("block_f", 512)
    if hc_buckets > 1:
        from repro.core.plan import bucket_geometry, gmo_layout
        geometry = bucket_geometry(cap_rows, cap_heads, 1, hc_buckets)
        # Live-head mass proxy for the sort's tie-break ranking (the plan
        # build uses the strategy's row_score here).
        score = jnp.sum(rows, axis=-1).astype(jnp.float32)
        gmo, _, _ = gmo_layout(row_ids[None], row_cnt.reshape(1),
                               head_ids[None], head_cnt[None], score[None],
                               geometry, t)
        out = gemm_o_sparse_bucketed_kernel(
            o_heads, w, bias, gmo["gmo_rows"][0], gmo["gmo_src"][0],
            gmo["gmo_head_ids"][0], gmo["gmo_head_cnt"][0], geometry,
            block_rows=block_rows, block_f=block_f, interpret=interpret)
        return jnp.where(row_cnt > 0, out, bias)
    out = gemm_o_sparse_kernel(o_heads, w, bias, row_ids, head_ids, head_cnt,
                               block_rows=block_rows, block_f=block_f,
                               interpret=interpret)
    return jnp.where(row_cnt > 0, out, bias)


@functools.partial(jax.jit, static_argnames=("block", "cap", "interpret"))
def taylor_reuse(
    derivs: jax.Array,       # (D+1, BH, N, d)
    coef: jax.Array,         # (D+1,) f32
    base: jax.Array,         # (BH, N, d)
    cached_mask: jax.Array,  # (BH, T) True = cached (forecast these blocks)
    *,
    block: int,
    cap: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> jax.Array:
    interpret = (not on_tpu()) if interpret is None else interpret
    t = cached_mask.shape[-1]
    cap = t if cap is None else cap
    ids, cnt = active_indices(cached_mask, cap)
    out = taylor_reuse_kernel(derivs, coef.reshape(1, -1).astype(jnp.float32),
                              base, ids, block=block, interpret=interpret)
    any_cached = (cnt > 0)[:, None, None]
    return jnp.where(any_cached, out, base)

"""FlashOmni general sparse attention — Pallas TPU kernels (paper §3.4).

Two variants of the paper's Algorithm 1, adapted to the TPU execution model
(DESIGN §2):

``flashomni_attention_csr``  (default, TPU-native structural skipping)
    The KV reduction runs over per-row CSR column lists for only the
    ``Cq`` live Q blocks (static capacity), so skipped tiles are never
    read.  Cached rows are left untouched via input/output aliasing of the
    ``o_reuse`` tensor (their forecast value is produced by the
    ``taylor_reuse`` element-wise kernel, the paper's "alternatively, an
    elementwise kernel can be invoked").  Grid ``(BH, Cq)``, one step per
    q-block row, whose body walks the row's live KV blocks, a few at a
    time, slicing them out of VMEM; the row's id list arrives in SMEM as
    its own block, so SMEM use does not grow with ``BH·Cq``.  Where one
    head's K and V fit :data:`RESIDENT_VMEM_BYTES` they are one VMEM block
    each, fetched once per head (the resident walk).  Longer sequences
    cut them into ``W`` windows (:func:`csr_windows`), grid
    ``(BH, Cq, W)``: each step walks the run of the row's ascending list
    that lies in its window, carrying the online softmax across windows
    in VMEM scratch.  Padding rows (past ``q_cnt``) do nothing.  The walk
    makes one online-softmax update per group of up to ``G``
    (:func:`walk_group`) live KV blocks in ascending id order: one
    ``(bq, g·bkv)`` score block, one max/sum chain and rescale, one
    ``g·bkv``-deep P·V.  A window cut restarts the grouping, so the
    resident and windowed walks sum in different orders.

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
    into ``kv_row_cnt`` at plan build, so the bucketed and uniform
    kernels compute the same tiles; the bucketed one updates one tile a
    grid step.

``flashomni_attention_symbols``  (paper-faithful predication)
    The grid covers every ``(i, j)`` tile; each program decodes the packed
    uint8 symbols with the paper's bitwise ``F``/``J`` and predicates
    compute with ``@pl.when`` — including the fused cache-then-reuse copy
    branch (Algorithm 1 lines 5–10).  Demonstrates symbol-decode fidelity;
    DMA traffic is NOT reduced (documented GPU→TPU non-transfer).

Numerical contract: each path is exact against its own ordered
reference (the same updates over the same tiles in the same order, in
f32), and the paths agree with each other, and with
:func:`repro.kernels.ref.attention_ref`, to f32 rounding.  All validate
in ``interpret=True`` mode; on real v5e the CSR variants are the serving
path.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


__all__ = [
    "RESIDENT_VMEM_BYTES",
    "csr_windows",
    "flashomni_attention_csr",
    "flashomni_attention_csr_bucketed",
    "flashomni_attention_symbols",
]

_NEG_INF = -1e30
_LANES = 128  # TPU vreg lane count: m/l kept (bq, 128)-shaped.
# VMEM the CSR walk may give to one window of a head's K and V, double-
# buffered, of v5e's 128 MiB a core.  A call whose window passes
# _SCOPED_KV_BYTES (what fits v5e's 16 MiB default scoped limit beside the
# Q/O blocks and the body's f32 temporaries) raises its limit by as much.
RESIDENT_VMEM_BYTES = 48 * 2**20
_SCOPED_KV_BYTES = 12 * 2**20
_SCOPED_VMEM_BYTES = 16 * 2**20
# KV blocks one online-softmax update of the CSR walk takes (the last
# group of a row takes the rest).  A call on one v5e took 4.51 / 2.77 /
# 2.04 ms at flux's shape and 48.5 / 26.3 / 17.3 ms at a hunyuan shard's
# with 4 / 8 / 16, against 3.85 / 40.1 ms for one update per block.
_WALK_GROUP = 16


# ---------------------------------------------------------------------------
# CSR variant
# ---------------------------------------------------------------------------

def _scores(q, k, scale: float):
    """(bq, bk) scaled scores of f32 ``q`` (bq, d) against a K block."""
    return jax.lax.dot_general(q, k.astype(jnp.float32),
                               (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32) * scale


def _online_softmax(s, v, m, l, acc):
    """One online-softmax step over a block's scores ``s`` and V block.
    ``m`` and ``l`` are lane-broadcast (bq, 128), ``acc`` f32 (bq, d).
    Returns the updated ``(m, l, acc)``."""
    m_prev = m[:, :1]                                       # (bq, 1)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)                         # (bq, 1)
    l = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc = acc * alpha + jax.lax.dot(p, v.astype(jnp.float32),
                                    preferred_element_type=jnp.float32)
    return jnp.broadcast_to(m_new, m.shape), l, acc


def _normalize(acc, l):
    l = l[:, :1]
    return acc / jnp.where(l == 0.0, 1.0, l)                # fully-skipped row guard


def _init(acc_ref, m_ref, l_ref):
    acc_ref[...] = jnp.zeros_like(acc_ref)
    m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)


def _tile_update(q, k, v, acc_ref, m_ref, l_ref, scale: float):
    """:func:`_online_softmax` on the scratch refs of a grid kernel."""
    m_ref[...], l_ref[...], acc_ref[...] = _online_softmax(
        _scores(q, k, scale), v, m_ref[...], l_ref[...], acc_ref[...])


def _finalize(o_ref, acc_ref, l_ref):
    o_ref[0] = _normalize(acc_ref[...], l_ref[...]).astype(o_ref.dtype)


def _group_update(q, ks, vs, carry, scale: float):
    """One online-softmax update over a group of KV blocks: the row's
    scores against all of them as one ``(bq, g·bkv)`` block, one max/sum
    chain and one rescale of ``acc``, then one ``g·bkv``-deep P·V.  With
    one block it is the per-tile update."""
    k = ks[0] if len(ks) == 1 else jnp.concatenate(ks)
    v = vs[0] if len(vs) == 1 else jnp.concatenate(vs)
    return _online_softmax(_scores(q, k, scale), v, *carry)


def walk_group(ckv: int) -> int:
    """KV blocks the CSR walk fuses into one update, for rows of ``ckv``
    slots."""
    return min(_WALK_GROUP, ckv)


def _walk(q, carry, ids_ref, k_ref, v_ref, lo, hi, base, *, scale: float,
          block_kv: int, group: int):
    """The online softmax ``carry`` ``(m, l, acc)`` over the row's slots
    ``[lo, hi)``, in ascending order, ``group`` KV blocks an update
    (:func:`_group_update`): full groups straight-line in a loop, then the
    last, partial one by its width (:func:`_switch`).  ``base`` is the
    first KV block of the window ``k_ref`` / ``v_ref`` hold."""
    def update(j0, width, carry):
        rows = [pl.ds(pl.multiple_of(
            (ids_ref[0, 0, j0 + i] - base) * block_kv, block_kv), block_kv)
            for i in range(width)]
        return _group_update(q, [k_ref[0, r, :] for r in rows],
                             [v_ref[0, r, :] for r in rows], carry, scale)

    full = (hi - lo) // group
    carry = jax.lax.fori_loop(
        0, full, lambda g, c: update(lo + g * group, group, c), carry)
    j0 = lo + full * group
    return _switch(hi - j0, [lambda c: c] + [functools.partial(update, j0, w)
                                             for w in range(1, group)], carry)


def _switch(index, branches, carry):
    """``branches[index](carry)`` through a balanced tree of ``lax.cond``:
    ``lax.switch`` nests one conditional per case, and Mosaic's layout pass
    overflows its stack on the 16 of a group of 16."""
    if len(branches) == 1:
        return branches[0](carry)
    mid = len(branches) // 2
    return jax.lax.cond(index < mid,
                        lambda c: _switch(index, branches[:mid], c),
                        lambda c: _switch(index - mid, branches[mid:], c),
                        carry)


def _csr_kernel(
    # scalar prefetch
    q_ids_ref, q_src_ids_ref, kv_cnt_ref, q_cnt_ref,
    # inputs
    ids_ref,                            # this row's (1, 1, Ckv) KV ids, SMEM
    q_ref, k_ref, v_ref, o_reuse_ref,   # k/v: one window (1, window·bkv, d)
    # outputs
    o_ref,
    # scratch: (m, l, acc) across the windows of a row (windowed walk only)
    *carry_refs,
    scale: float,
    ckv: int,
    block_kv: int,
    window: int,
):
    bh, c = pl.program_id(0), pl.program_id(1)
    if carry_refs:     # read at the top level: the interpreter wants it so
        w, last = pl.program_id(2), pl.num_programs(2) - 1
    walk = functools.partial(_walk, ids_ref=ids_ref, k_ref=k_ref,
                             v_ref=v_ref, scale=scale, block_kv=block_kv,
                             group=walk_group(ckv))

    # Padding rows repeat the last live row's output block, which stays
    # resident across consecutive steps with the same index: leaving it
    # unwritten keeps what that row wrote.
    @pl.when(c < q_cnt_ref[bh])
    def _():
        q = q_ref[0].astype(jnp.float32)
        n = kv_cnt_ref[bh, c]
        bq, d = q.shape
        init = (jnp.full((bq, _LANES), _NEG_INF, jnp.float32),
                jnp.zeros((bq, _LANES), jnp.float32),
                jnp.zeros((bq, d), jnp.float32))
        if not carry_refs:
            # Resident: (m, l, acc) ride the loop carry rather than VMEM
            # scratch: 18% less time a call at one block an iteration
            # (flux width, v5e).
            m, l, acc = walk(q, init, lo=0, hi=n, base=0)
            o_ref[0] = _normalize(acc, l).astype(o_ref.dtype)
            return
        m_ref, l_ref, acc_ref = carry_refs

        @pl.when(w == 0)
        def _():
            m_ref[...], l_ref[...], acc_ref[...] = init

        # The row's ids in this window are a run of its ascending list.
        lo = _first_at_least(ids_ref, n, w * window, ckv)
        hi = _first_at_least(ids_ref, n, (w + 1) * window, ckv)
        m, l, acc = walk(q, (m_ref[...], l_ref[...], acc_ref[...]),
                         lo=lo, hi=hi, base=w * window)
        m_ref[...], l_ref[...], acc_ref[...] = m, l, acc

        @pl.when(w == last)
        def _():
            o_ref[0] = _normalize(acc, l).astype(o_ref.dtype)


def _first_at_least(ids_ref, n, bound, ckv: int):
    """The first slot of the ascending ``ids_ref[0, 0, :n]`` whose id is at
    least ``bound`` (``n`` if none): a binary search in SMEM."""
    def halve(_, lr):
        lo, hi = lr
        mid = jnp.minimum((lo + hi) // 2, ckv - 1)
        right = ids_ref[0, 0, mid] < bound
        more = lo < hi
        return (jnp.where(more & right, mid + 1, lo),
                jnp.where(more & ~right, mid, hi))
    return jax.lax.fori_loop(0, ckv.bit_length(), halve, (0, n))[0]


def _kv_window_bytes(blocks: int, block_kv: int, d: int,
                     itemsize: int) -> int:
    """VMEM of a window of ``blocks`` KV blocks of K and V, double-buffered
    (lanes padded to 128)."""
    return 4 * blocks * block_kv * (-(-d // _LANES) * _LANES) * itemsize


def csr_windows(n_kv: int, d: int, itemsize: int, block_kv: int) -> int:
    """How many VMEM windows the CSR walk cuts a head's K and V into: the
    fewest that each fit :data:`RESIDENT_VMEM_BYTES`.  One is the
    resident walk."""
    per_window = RESIDENT_VMEM_BYTES // _kv_window_bytes(1, block_kv, d,
                                                         itemsize)
    return -(-(n_kv // block_kv) // max(1, per_window))


def flashomni_attention_csr(
    q: jax.Array,             # (BH, N_q, d) — full OR compact (layout fusion)
    k: jax.Array,             # (BH, N_kv, d)
    v: jax.Array,             # (BH, N_kv, d)
    o_reuse: jax.Array,       # (BH, N, d) — cached/forecast baseline (aliased)
    q_ids: jax.Array,         # (BH, Cq) int32 live q-block ids (output layout)
    kv_ids: jax.Array,        # (BH, Cq, Ckv) int32 per-row live kv-block ids
    kv_cnt: jax.Array,        # (BH, Cq) int32
    q_cnt: jax.Array,         # (BH,) int32 live rows per head
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
    ``q_ids`` (full-layout Q).

    Grid ``(BH, Cq)``, or ``(BH, Cq, W)`` where K and V take
    :func:`csr_windows` ``W > 1`` windows: each step walks the row's live
    KV blocks that lie in its window, slicing them out of VMEM.  The
    row's id list reaches SMEM as its own block each step, so SMEM holds
    O(Ckv) ids whatever B·H·Cq.  Padding rows (past ``q_cnt``) must
    repeat the last live row's ids, as
    :func:`repro.core.symbols.active_indices` pads them.  Block
    ``q_ids[bh, 0]`` of a head with ``q_cnt`` 0 is left undefined:
    callers keep ``o_reuse`` for such heads."""
    return _csr_call(q, k, v, o_reuse, q_ids, kv_ids, kv_cnt, q_cnt,
                     block_q=block_q, block_kv=block_kv, scale=scale,
                     interpret=interpret, q_src_ids=q_src_ids,
                     windows=csr_windows(k.shape[1], k.shape[2],
                                         k.dtype.itemsize, block_kv))


def _csr_call(q, k, v, o_reuse, q_ids, kv_ids, kv_cnt, q_cnt, *,
              block_q: int, block_kv: int, scale: Optional[float],
              interpret: bool, q_src_ids: Optional[jax.Array],
              windows: int) -> jax.Array:
    bhs, n_q, d = q.shape
    n_kv = k.shape[1]
    assert n_q % block_q == 0 and n_kv % block_kv == 0
    assert o_reuse.shape[1] % block_q == 0
    cq, ckv = q_ids.shape[1], kv_ids.shape[2]
    scale = (d ** -0.5) if scale is None else scale
    q_src_ids = q_ids if q_src_ids is None else q_src_ids
    n_blocks = n_kv // block_kv
    window = -(-n_blocks // windows)                 # KV blocks a window
    windows = -(-n_blocks // window)
    kernel = functools.partial(_csr_kernel, scale=scale, ckv=ckv,
                               block_kv=block_kv, window=window)
    prefetch = (q_ids, q_src_ids, kv_cnt, q_cnt)

    if windows == 1:
        grid = (bhs, cq)
        scratch = []

        def kv_map(bh, c, *_):
            return (bh, 0, 0)
    else:
        grid = (bhs, cq, windows)
        scratch = [pltpu.VMEM((block_q, _LANES), jnp.float32),
                   pltpu.VMEM((block_q, _LANES), jnp.float32),
                   pltpu.VMEM((block_q, d), jnp.float32)]

        def kv_map(bh, c, w, q_ids_ref, q_src_ref, kv_cnt_ref, q_cnt_ref):
            # A padding row stays on the last window: no DMA.
            return (bh, jnp.where(c < q_cnt_ref[bh], w, windows - 1), 0)

    # The row's list as an (1, 1, Ckv) block: its last two dimensions are
    # the array's, which Mosaic requires of a block it cannot tile.
    def ids_map(bh, c, *_):
        return (bh * cq + c, 0, 0)

    # Index maps take the grid indices, then the four prefetched refs.
    def q_map(bh, c, *rest):
        q_ids_ref, q_src_ids_ref = rest[-4:-2]
        return (bh, q_src_ids_ref[bh, c], 0)

    def o_map(bh, c, *rest):
        return (bh, rest[-4][bh, c], 0)

    kv_spec = pl.BlockSpec((1, window * block_kv, d), kv_map)
    kv_bytes = _kv_window_bytes(window, block_kv, d, k.dtype.itemsize)
    vmem_limit = None if kv_bytes <= _SCOPED_KV_BYTES else \
        _SCOPED_VMEM_BYTES - _SCOPED_KV_BYTES + kv_bytes
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch),
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, 1, ckv), ids_map, memory_space=pltpu.SMEM),
                pl.BlockSpec((1, block_q, d), q_map),
                kv_spec,
                kv_spec,
                pl.BlockSpec((1, block_q, d), o_map),       # o_reuse (aliased)
            ],
            out_specs=pl.BlockSpec((1, block_q, d), o_map),
            scratch_shapes=scratch,
        ),
        out_shape=jax.ShapeDtypeStruct(o_reuse.shape, o_reuse.dtype),
        # NB: alias indices count the scalar-prefetch operands too.
        input_output_aliases={len(prefetch) + 4: 0},        # o_reuse -> out
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",) + ("arbitrary",) * (len(grid) - 1),
            vmem_limit_bytes=vmem_limit,
        ),
        interpret=interpret,
        name="flashomni_csr_attention",
    )(*prefetch, kv_ids.reshape(bhs * cq, 1, ckv), q, k, v, o_reuse)


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
    def _():
        _init(acc_ref, m_ref, l_ref)

    @pl.when(jof < kv_cnt_ref[b, r])
    def _():
        _tile_update(q_ref[0].astype(jnp.float32), k_ref[0], v_ref[0],
                     acc_ref, m_ref, l_ref, scale)

    @pl.when(slast_ref[s] == 1)
    def _():
        _finalize(o_ref, acc_ref, l_ref)


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
    def _():
        _init(acc_ref, m_ref, l_ref)

    # Cache-then-Reuse (Algorithm 1 lines 5-10): fused element-wise copy of
    # the forecast feature, then the CTA-equivalent returns.
    @pl.when((f_live == 0) & (j == t_kv - 1))
    def _reuse():
        o_ref[0] = o_reuse_ref[0]

    # Compute-on-Demand (lines 11-19) with reduction-axis skipping (line 13).
    @pl.when((f_live == 1) & (j_live == 1))
    def _():
        _tile_update(q_ref[0].astype(jnp.float32), k_ref[0], v_ref[0],
                     acc_ref, m_ref, l_ref, scale)

    @pl.when((f_live == 1) & (j == t_kv - 1))
    def _():
        _finalize(o_ref, acc_ref, l_ref)


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

"""Pallas kernel sweeps vs pure-jnp oracles (interpret=True on CPU).

Per task spec: sweep shapes/dtypes per kernel, assert_allclose vs ref.py.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _csr_oracle import _group_by_group, _tile_by_tile
from repro.core.symbols import active_indices
from repro.kernels import ops, ref
from repro.kernels.flashomni_attention import _WALK_GROUP


def _attn_inputs(key, bh, n, d, bq, bk, p_c=0.6, p_s=0.7, dtype=jnp.float32):
    tq, tkv = n // bq, n // bk
    ks = jax.random.split(jax.random.PRNGKey(key), 6)
    q = jax.random.normal(ks[0], (bh, n, d), dtype)
    k = jax.random.normal(ks[1], (bh, n, d), dtype)
    v = jax.random.normal(ks[2], (bh, n, d), dtype)
    o_reuse = jax.random.normal(ks[3], (bh, n, d), dtype)
    m_c = jax.random.bernoulli(ks[4], p_c, (bh, tq))
    m_s = jax.random.bernoulli(ks[5], p_s, (bh, tq, tkv)).at[..., 0].set(True)
    return q, k, v, m_c, m_s, o_reuse


ATTN_SWEEP = [
    # (BH, N, d, bq, bk, dtype, tol)
    (2, 128, 32, 16, 16, jnp.float32, 2e-5),
    (1, 256, 64, 32, 16, jnp.float32, 2e-5),
    (3, 256, 128, 64, 64, jnp.float32, 2e-5),
    (2, 128, 64, 16, 32, jnp.bfloat16, 3e-2),
]


@pytest.mark.parametrize("variant", ["csr", "symbols"])
@pytest.mark.parametrize("bh,n,d,bq,bk,dtype,tol", ATTN_SWEEP)
def test_flashomni_attention_vs_ref(variant, bh, n, d, bq, bk, dtype, tol):
    q, k, v, m_c, m_s, o_reuse = _attn_inputs(bh * n, bh, n, d, bq, bk, dtype=dtype)
    want = ref.attention_ref(q, k, v, m_c, m_s, o_reuse, block_q=bq, block_kv=bk)
    got = ops.flashomni_attention(q, k, v, m_c, m_s, o_reuse,
                                  block_q=bq, block_kv=bk, variant=variant)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=tol, rtol=tol)


@pytest.mark.parametrize("variant", ["csr", "symbols"])
def test_attention_all_cached_and_all_live(variant):
    q, k, v, m_c, m_s, o_reuse = _attn_inputs(7, 2, 128, 32, 16, 16)
    for mc in [jnp.zeros_like(m_c), jnp.ones_like(m_c)]:
        want = ref.attention_ref(q, k, v, mc, m_s, o_reuse, block_q=16, block_kv=16)
        got = ops.flashomni_attention(q, k, v, mc, m_s, o_reuse,
                                      block_q=16, block_kv=16, variant=variant)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def test_attention_csr_with_capacity():
    q, k, v, m_c, m_s, o_reuse = _attn_inputs(9, 2, 256, 32, 32, 32)
    tq = m_c.shape[-1]
    # capacity == max live count across bh -> still exact
    cap = int(m_c.sum(-1).max())
    want = ref.attention_ref(q, k, v, m_c, m_s, o_reuse, block_q=32, block_kv=32)
    got = ops.flashomni_attention(q, k, v, m_c, m_s, o_reuse, block_q=32,
                                  block_kv=32, cap_q=cap, cap_kv=tq)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


# ---------------------------------------------------------------------------
# CSR attention: resident walk vs windowed walk vs their grouped updates
# ---------------------------------------------------------------------------

def _csr_plan(seed, bh, t, cap_q, cap_kv, p_c=0.6, p_s=0.5):
    """Per-row CSR lists as the plan builds them, with a live row that has
    no KV block (head 1, first live row) and padding rows past q_cnt."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 2)
    m_c = jax.random.bernoulli(ks[0], p_c, (bh, t)).at[1, 0].set(True)
    m_s = jax.random.bernoulli(ks[1], p_s, (bh, t, t)).at[1, 0].set(False)
    q_ids, q_cnt = active_indices(m_c, cap_q)
    rows = jnp.take_along_axis(m_s, q_ids[..., None], axis=-2)
    kv_ids, kv_cnt = active_indices(rows, cap_kv)
    return q_ids, q_cnt, kv_ids, kv_cnt


CSR_PATH_CASES = {
    # name: (bh, n, d, blk, cap_q, cap_kv, compact)
    "full_lists": (3, 256, 64, 32, 8, 8, False),
    "cap_kv_truncates": (3, 256, 64, 32, 8, 3, False),
    "padding_rows": (3, 256, 32, 16, 16, 16, False),
    "compact_q": (3, 256, 64, 32, 8, 5, True),
    "rows_past_one_group": (2, 640, 32, 16, 28, 40, False),
}


@pytest.mark.parametrize("case", CSR_PATH_CASES)
def test_windowed_walk_matches_resident_bitwise(case):
    """The resident walk and K/V cut into 3 windows (the last one short)
    or one per block each give the bits of their own grouped updates
    (``walk_group`` blocks an update, restarting at each window cut), and
    agree with each other to f32 rounding.  One window per block is one
    tile an update: the bucketed kernel's order."""
    from repro.kernels.flashomni_attention import _csr_call, walk_group
    bh, n, d, blk, cap_q, cap_kv, compact = CSR_PATH_CASES[case]
    q, k, v, _, _, o_reuse = _attn_inputs(11, bh, n, d, blk, blk)
    q_ids, q_cnt, kv_ids, kv_cnt = _csr_plan(5, bh, n // blk, cap_q, cap_kv)
    assert int(q_cnt.min()) < cap_q            # some heads carry padding rows
    assert int(kv_cnt[1, 0]) == 0              # a live row with no KV block
    q_src = None
    if compact:
        # Live Q blocks packed at the front of a compact (cap_q·blk, d) Q.
        q_src = jnp.broadcast_to(jnp.arange(cap_q, dtype=jnp.int32),
                                 q_ids.shape)
        q_src = jnp.minimum(q_src, jnp.maximum(q_cnt[:, None] - 1, 0))
        q = q[:, :cap_q * blk]
    n_blocks = n // blk
    assert n_blocks % 3                        # the last window is short
    out = {w: _csr_call(q, k, v, o_reuse, q_ids, kv_ids, kv_cnt, q_cnt,
                        block_q=blk, block_kv=blk, scale=None,
                        interpret=True, q_src_ids=q_src, windows=w)
           for w in (1, 3, n_blocks)}
    lists = (q, k, v, o_reuse, q_ids, kv_ids, kv_cnt, q_cnt, blk,
             q_ids if q_src is None else q_src)
    for w in (1, 3, n_blocks):
        np.testing.assert_array_equal(
            np.asarray(out[w]),
            _group_by_group(*lists, group=walk_group(cap_kv),
                            window=-(-n_blocks // w)))
    for w in (3, n_blocks):
        np.testing.assert_allclose(np.asarray(out[w]), np.asarray(out[1]),
                                   atol=2e-5, rtol=2e-5)
    np.testing.assert_array_equal(np.asarray(out[n_blocks]),
                                  _tile_by_tile(*lists))
    # The row with no KV block writes zeros; cached blocks keep o_reuse.
    zero_blk = int(q_ids[1, 0])
    np.testing.assert_array_equal(
        np.asarray(out[1][1, zero_blk * blk:(zero_blk + 1) * blk]), 0.0)
    live = np.zeros((bh, n_blocks), bool)
    for b in range(bh):
        live[b, np.asarray(q_ids[b, :int(q_cnt[b])])] = True
    cached = np.repeat(~live, blk, axis=1)
    np.testing.assert_array_equal(np.asarray(out[1])[cached],
                                  np.asarray(o_reuse)[cached])


@pytest.mark.parametrize("tail", range(_WALK_GROUP))
def test_walk_last_group_takes_the_rows_remainder(tail):
    """Rows of ``G + tail`` and ``tail`` live KV blocks (``G`` the walk
    group): full groups, then one update of the remainder's width, bit for
    bit as the grouped updates and within 2e-5 of the XLA oracle.  At
    ``tail`` 0 the second row is live with no KV block and writes zeros;
    padding rows past ``q_cnt`` write nothing."""
    from repro.kernels.flashomni_attention import _csr_call, walk_group
    g = _WALK_GROUP
    bh, blk, d, t, cap_q = 2, 16, 32, 2 * g, 4
    n = t * blk
    assert walk_group(t) == g
    q, k, v, _, _, o_reuse = _attn_inputs(13 + tail, bh, n, d, blk, blk)
    rng = np.random.default_rng(tail)
    counts = [[g + tail, tail], [2 * g - 1 - tail]]
    q_ids = np.zeros((bh, cap_q), np.int32)
    q_cnt = np.array([len(c) for c in counts], np.int32)
    kv_ids = np.zeros((bh, cap_q, t), np.int32)
    kv_cnt = np.zeros((bh, cap_q), np.int32)
    m_c = np.zeros((bh, t), bool)
    m_s = np.zeros((bh, t, t), bool)
    for b, rows in enumerate(counts):
        q_ids[b] = np.sort(rng.choice(t, cap_q, replace=False))
        q_ids[b, len(rows):] = q_ids[b, len(rows) - 1]   # padding rows
        for c, cnt in enumerate(rows):
            ids = np.sort(rng.choice(t, cnt, replace=False))
            kv_ids[b, c, :cnt] = ids
            kv_ids[b, c, cnt:] = ids[-1] if cnt else 0
            kv_cnt[b, c] = cnt
            m_c[b, q_ids[b, c]] = True
            m_s[b, q_ids[b, c], ids] = True
        kv_ids[b, len(rows):] = kv_ids[b, len(rows) - 1]
        kv_cnt[b, len(rows):] = kv_cnt[b, len(rows) - 1]
    plan = [jnp.asarray(a) for a in (q_ids, kv_ids, kv_cnt, q_cnt)]
    got = np.asarray(_csr_call(q, k, v, o_reuse, *plan, block_q=blk,
                               block_kv=blk, scale=None, interpret=True,
                               q_src_ids=None, windows=1))
    np.testing.assert_array_equal(
        got, _group_by_group(q, k, v, o_reuse, *plan, blk, plan[0],
                             group=g))
    want = np.asarray(ref.attention_ref(
        q, k, v, jnp.asarray(m_c), jnp.asarray(m_s), o_reuse,
        block_q=blk, block_kv=blk))
    empty = np.zeros((bh, n), bool)            # live rows with no KV block
    for b, rows in enumerate(counts):
        for c, cnt in enumerate(rows):
            if not cnt:
                empty[b, q_ids[b, c] * blk:(q_ids[b, c] + 1) * blk] = True
    assert empty.any() == (tail == 0)
    np.testing.assert_array_equal(got[empty], 0.0)
    np.testing.assert_allclose(got[~empty], want[~empty],
                               atol=2e-5, rtol=2e-5)


def test_csr_scalar_prefetch_does_not_grow_with_the_id_lists():
    """The per-row KV id lists reach SMEM one row per grid step: every
    scalar-prefetched operand is at most B·H·Cq long, whatever Ckv."""
    from repro.kernels.flashomni_attention import _csr_call
    bh, n, d, blk, cap_q = 2, 256, 32, 16, 16
    q, k, v, _, _, o_reuse = _attn_inputs(3, bh, n, d, blk, blk)
    for cap_kv, windows in ((4, 1), (16, 1), (16, 4)):
        q_ids, q_cnt, kv_ids, kv_cnt = _csr_plan(2, bh, n // blk, cap_q,
                                                 cap_kv)
        jaxpr = jax.make_jaxpr(lambda *a: _csr_call(
            *a, block_q=blk, block_kv=blk, scale=None, interpret=True,
            q_src_ids=None, windows=windows))(
                q, k, v, o_reuse, q_ids, kv_ids, kv_cnt, q_cnt)
        (eqn,) = [e for e in jaxpr.eqns if e.primitive.name == "pallas_call"]
        n_pre = eqn.params["grid_mapping"].num_index_operands
        sizes = [int(np.prod(x.aval.shape)) for x in eqn.invars[:n_pre]]
        assert n_pre == 4 and max(sizes) <= bh * cap_q, sizes


def test_resident_walk_keeps_o_reuse_on_a_dead_head(monkeypatch):
    """A head with q_cnt 0 runs no row; ``PallasBackend.attention``'s guard
    returns its ``o_reuse``, and the other heads agree with the windowed
    walk's to f32 rounding (its groups restart at the window cuts)."""
    from repro.core import EngineConfig, MaskConfig
    from repro.core.backend import PallasBackend
    from repro.core.plan import build_dispatch_plan
    from repro.kernels import flashomni_attention as fa
    b, h, n, d, blk = 1, 3, 128, 32, 16
    t = n // blk
    cfg = EngineConfig(mask=MaskConfig(pool=blk, block_q=blk, block_kv=blk),
                       cap_q_frac=1.0, cap_kv_frac=1.0, backend="pallas")
    ks = jax.random.split(jax.random.PRNGKey(4), 6)
    q, k, v, o_reuse = (jax.random.normal(kk, (b, h, n, d)) for kk in ks[:4])
    m_c = jax.random.bernoulli(ks[4], 0.6, (b, h, t)).at[:, 0].set(False)
    m_s = jax.random.bernoulli(ks[5], 0.5, (b, h, t, t))
    plan = build_dispatch_plan(m_c, m_s, cfg, n)
    assert int(plan.q_cnt[0, 0]) == 0
    spec = cfg.caps(n)
    attn = lambda: PallasBackend(interpret=True).attention(
        q, k, v, o_reuse, plan, spec)
    resident = attn()
    monkeypatch.setattr(fa, "csr_windows", lambda *a: 3)
    windowed = attn()
    np.testing.assert_allclose(np.asarray(resident), np.asarray(windowed),
                               atol=2e-5, rtol=2e-5)
    np.testing.assert_array_equal(np.asarray(resident[:, 0]),
                                  np.asarray(o_reuse[:, 0]))


@pytest.mark.parametrize("n_kv,d,itemsize,windows", [
    (4608, 128, 2, 1),           # flux width, bf16: 4.72 MB
    (4608, 128, 4, 1),           # flux width, f32: 9.44 MB
    (33_088, 128, 2, 1),         # hunyuan's per-shard buffer: 33.9 MB
    (66_048, 128, 2, 2),         # 66K keys on one chip: 67.6 MB
    (24_576, 64, 4, 1),          # lanes pad d 64 to 128: 50.3 MB ...
    (24_640, 64, 4, 2),          # ... and count: one block more
])
def test_csr_path_is_chosen_from_the_shapes(n_kv, d, itemsize, windows):
    from repro.kernels.flashomni_attention import csr_windows
    assert csr_windows(n_kv, d, itemsize, 64) == windows


# ---------------------------------------------------------------------------
# Dense attention (Update and engine-off steps): blockwise kernel vs XLA
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("bh,n_q,n_kv,d,dtype,tol", [
    (2, 128, 128, 64, jnp.float32, 1e-5),
    (3, 80, 80, 32, jnp.float32, 1e-5),       # 16 text + 64 image tokens
    (2, 200, 328, 64, jnp.float32, 1e-5),     # no tile divides either
    (2, 256, 384, 128, jnp.bfloat16, 3e-2),
])
def test_dense_attention_kernel_matches_xla(bh, n_q, n_kv, d, dtype, tol):
    from repro.core.attention import dense_attention
    from repro.kernels.dense_attention import flashomni_dense_attention
    ks = jax.random.split(jax.random.PRNGKey(n_q + n_kv), 3)
    q = (2 * jax.random.normal(ks[0], (bh, n_q, d))).astype(dtype)
    k = (2 * jax.random.normal(ks[1], (bh, n_kv, d))).astype(dtype)
    v = jax.random.normal(ks[2], (bh, n_kv, d)).astype(dtype)
    got = flashomni_dense_attention(q, k, v, interpret=True)
    assert got.shape == q.shape and got.dtype == dtype
    want = dense_attention(*(a.astype(jnp.float32) for a in (q, k, v)))
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want), atol=tol, rtol=tol)


def test_dense_attention_tiles_follow_the_lengths():
    """Query tiles divide the served lengths; keys split evenly into
    tiles of at most 1,536 columns (the 33K shard's 33,024 keys padded to
    22 of them and masked); a length no tile divides is padded."""
    from repro.kernels.dense_attention import dense_tiles
    assert dense_tiles(4608, 4608) == (768, 1536)
    assert dense_tiles(33_024 // 4, 33_024) == (688, 1536)
    assert dense_tiles(200, 80) == (208, 128)


GEMM_SWEEP = [
    (128, 64, 128, 16, jnp.float32, 1e-4),
    (256, 128, 256, 32, jnp.float32, 1e-4),
    (128, 256, 512, 64, jnp.float32, 1e-4),
    (128, 64, 128, 16, jnp.bfloat16, 5e-2),
]


@pytest.mark.parametrize("n,k,f,blk,dtype,tol", GEMM_SWEEP)
def test_gemm_q_vs_ref(n, k, f, blk, dtype, tol):
    ks = jax.random.split(jax.random.PRNGKey(n + k), 3)
    x = jax.random.normal(ks[0], (n, k), dtype)
    w = jax.random.normal(ks[1], (k, f), dtype)
    rm = jax.random.bernoulli(ks[2], 0.5, (n // blk,)).at[0].set(True)
    y, ids, cnt = ops.gemm_q(x, w, rm, block_rows=blk, interpret=True)
    want = ref.gemm_q_ref(x, w, ids, cnt, block=blk)
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(want, np.float32), atol=tol, rtol=tol)


@pytest.mark.parametrize("h,n,dh,f,blk,dtype,tol", [
    (4, 128, 32, 64, 16, jnp.float32, 1e-4),
    (8, 256, 64, 128, 32, jnp.float32, 1e-4),
    (2, 128, 128, 256, 64, jnp.float32, 1e-4),
    (4, 128, 64, 64, 16, jnp.bfloat16, 6e-2),
])
def test_gemm_o_vs_ref(h, n, dh, f, blk, dtype, tol):
    ks = jax.random.split(jax.random.PRNGKey(h * n), 4)
    oh = jax.random.normal(ks[0], (h, n, dh), dtype)
    w = jax.random.normal(ks[1], (h, dh, f), dtype)
    bias = jax.random.normal(ks[2], (n, f), dtype)
    t = n // blk
    m_ch = jax.random.bernoulli(ks[3], 0.6, (t, h))
    got = ops.gemm_o(oh, w, bias, m_ch, block_rows=blk, interpret=True)
    row_ids, row_cnt = active_indices(jnp.any(m_ch, -1), t)
    head_ids, head_cnt = active_indices(jnp.take(m_ch, row_ids, 0), h)
    want = ref.gemm_o_ref(oh, w, bias, row_ids, row_cnt, head_ids, head_cnt, block=blk)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=tol, rtol=tol)


def test_gemm_o_eq3_identity():
    """Eq. 3: live-head partial + cached-bias == full dense projection."""
    from repro.core.sparse_gemm import gemm_o_update_bias
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    h, n, dh, f, blk = 4, 64, 16, 32, 16
    oh = jax.random.normal(ks[0], (h, n, dh))
    w = jax.random.normal(ks[1], (h, dh, f))
    m_ch = jax.random.bernoulli(ks[2], 0.5, (n // blk, h))
    o_tok = oh.transpose(1, 0, 2)[None]                     # (1,N,H,dh)
    bias = gemm_o_update_bias(o_tok, w, m_ch[None], block=blk)[0]
    got = ops.gemm_o(oh, w, bias, m_ch, block_rows=blk, interpret=True)
    want = jnp.einsum("hnd,hdf->nf", oh, w)                 # full projection
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("d1,bh,n,d,blk", [(2, 2, 128, 32, 16), (4, 1, 64, 64, 16)])
def test_taylor_reuse_vs_ref(d1, bh, n, d, blk):
    ks = jax.random.split(jax.random.PRNGKey(d1), 3)
    derivs = jax.random.normal(ks[0], (d1, bh, n, d))
    coef = jax.random.normal(ks[1], (d1,))
    base = jax.random.normal(ks[2], (bh, n, d))
    cmask = jax.random.bernoulli(ks[0], 0.5, (bh, n // blk))
    got = ops.taylor_reuse(derivs, coef, base, cmask, block=blk, interpret=True)
    want_f = ref.taylor_reuse_ref(derivs, coef)
    live = jnp.repeat(cmask, blk, axis=-1)
    want = jnp.where(live[..., None], want_f, base)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)

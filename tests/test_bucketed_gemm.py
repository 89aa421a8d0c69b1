"""Occupancy-bucketed sparse GEMMs + plan-calibrated autotuner (ISSUE 8).

  * GEMM-O BIT parity: the bucketed two-level-grid kernel equals the
    uniform kernel on the SAME plan — ``gmo_layout`` folds any
    bucket-induced head clamp back into ``head_cnt``/``head_mask`` before
    extraction, so there is nothing left to diverge (no carve-outs) — on
    skewed plans including the adversarial one-full-row-among-empties;
  * padded-slot no-store: fully-cached rows keep their bias-aliased
    forecast value bit-exactly under both grids;
  * XLA parity: ``XlaBackend.gemm_o`` consumes the clamp-folded
    ``head_mask`` and agrees with both kernels within float tolerance;
  * GEMM-Q occupancy guard: the ``row_cnt`` scalar-prefetch guard leaves
    live slots bit-identical to the unguarded kernel and writes
    deterministic zeros into padding slots (the S_c early-exit analogue —
    GEMM-Q has no reduction occupancy to bucket);
  * plan plumbing: ``plan_from_state`` rebuilds ``occ_hist``/``gmo_*``
    bit-exactly; the int16 compaction covers the new id fields and
    ``widen()`` round-trips them;
  * autotuner: schema validation failure modes, the no-calibration → 1
    (uniform) fallback, selection determinism, and the one-executable-
    per-configuration budget (``kv_buckets = 0`` auto resolves purely
    from static config, and a mesh forces uniform).
"""

import copy
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import EngineConfig, plan_from_state, update_layer
from repro.core.backend import PallasBackend, XlaBackend
from repro.core.masks import MaskConfig
from repro.core.plan import (OCC_BINS, build_dispatch_plan,
                             occupancy_histogram)
from repro.kernels import ops
from repro.kernels.tuning import (CANDIDATE_BUCKETS, bucket_clamp_frac,
                                  bucket_slot_frac, kernel_tiles, load_table,
                                  select_kv_buckets, validate_table)

N_TEXT = 64


def _cfgs(kv_buckets=3, **kw):
    mk = dict(pool=32, block_q=16, block_kv=16, interval=4, order=1,
              warmup_steps=1)
    cfg_b = EngineConfig(mask=MaskConfig(**mk), cap_q_frac=1.0,
                         cap_kv_frac=1.0, cache_dtype=jnp.float32,
                         kv_buckets=kv_buckets, **kw)
    cfg_u = dataclasses.replace(cfg_b, kv_buckets=1)
    return cfg_b, cfg_u


def _gemm_o_inputs(seed, b, h, n, dh=32, f=64):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    o_tok = jax.random.normal(ks[0], (b, n, h, dh))
    w = jax.random.normal(ks[1], (h, dh, f))
    bias = jax.random.normal(ks[2], (b, n, f))
    return o_tok, w, bias


def _gemm_o_parity(m_c, m_s, *, seed=0, n=256):
    """Bucketed vs uniform Pallas GEMM-O on the same bucketed plan (BIT
    equal) vs XLA (allclose).  Returns the bucketed output + plan."""
    b, h, t = m_c.shape
    cfg_b, cfg_u = _cfgs()
    plan_b = build_dispatch_plan(m_c, m_s, cfg_b, n)
    plan_u = build_dispatch_plan(m_c, m_s, cfg_u, n)
    spec_b = cfg_b.caps(n)
    assert plan_b.gmo_rows is not None and plan_u.gmo_rows is None
    o_tok, w, bias = _gemm_o_inputs(seed, b, h, n)
    pb = PallasBackend(interpret=True)
    out_bkt = pb.gemm_o(o_tok, w, plan_b, bias, block=cfg_b.mask.pool,
                        spec=spec_b)
    # The SAME plan through the uniform kernel: head_cnt/head_mask already
    # fold the bucket clamp, so the two grids must agree bit-for-bit.
    out_uni = pb.gemm_o(o_tok, w, plan_b, bias, block=cfg_b.mask.pool,
                        spec=None)
    np.testing.assert_array_equal(np.asarray(out_bkt), np.asarray(out_uni))
    out_xla = XlaBackend().gemm_o(o_tok, w, plan_b, bias,
                                  block=cfg_b.mask.pool, spec=spec_b)
    np.testing.assert_allclose(np.asarray(out_bkt), np.asarray(out_xla),
                               atol=2e-5, rtol=2e-5)
    return out_bkt, plan_b, plan_u


def test_gemm_o_bucketed_skewed_bit_parity():
    """One all-heads row among single-head rows — the paper's GEMM-O skew."""
    b, h, t = 2, 4, 8
    m_c = jnp.zeros((b, h, t), bool)
    m_c = m_c.at[:, :, 0].set(True)                      # row 0: all heads
    m_c = m_c.at[:, 0, :].set(True)                      # head 0: all rows
    diag = jnp.eye(t, dtype=bool)
    m_s = jnp.broadcast_to(diag, (b, h, t, t)).at[..., 0].set(True)
    _gemm_o_parity(m_c, m_s, seed=1)


def test_gemm_o_adversarial_one_full_row_among_empties():
    """The single wide row must land in the wide bucket (no clamp), the
    near-empty rest in the narrow ones; clamp-free means the plan's
    head_cnt equals the uniform plan's and all three paths agree."""
    b, h, t = 1, 4, 8
    m_c = jnp.zeros((b, h, t), bool)
    m_c = m_c.at[0, :, 3].set(True)                      # the one full row
    m_c = m_c.at[0, 1, :].set(True)                      # one live head rest
    diag = jnp.eye(t, dtype=bool)
    m_s = jnp.broadcast_to(diag, (b, h, t, t)).at[..., 0].set(True)
    _, plan_b, plan_u = _gemm_o_parity(m_c, m_s, seed=2)
    np.testing.assert_array_equal(np.asarray(plan_b.head_cnt),
                                  np.asarray(plan_u.head_cnt))


def test_gemm_o_clamped_rows_stay_bit_consistent():
    """More full-width rows than wide slots: buckets DO clamp head lists.
    The clamp is folded back into head_cnt/head_mask, so bucketed,
    uniform and XLA still agree (the invariant has no carve-outs)."""
    b, h, t = 1, 4, 8
    m_c = jnp.ones((b, h, t), bool)                      # every row all-heads
    diag = jnp.eye(t, dtype=bool)
    m_s = jnp.broadcast_to(diag, (b, h, t, t)).at[..., 0].set(True)
    _, plan_b, plan_u = _gemm_o_parity(m_c, m_s, seed=3)
    assert int(jnp.sum(plan_u.head_cnt - plan_b.head_cnt)) > 0, \
        "plan should clamp head lists on this workload"


def test_gemm_o_padded_slots_keep_bias():
    """Fully-cached row blocks never store: the bias-aliased output keeps
    their forecast value BIT-exactly under both grids."""
    b, h, t, n = 1, 4, 8, 256
    m_c = jnp.zeros((b, h, t), bool)
    m_c = m_c.at[:, :, :2].set(True)                     # rows 2.. cached
    diag = jnp.eye(t, dtype=bool)
    m_s = jnp.broadcast_to(diag, (b, h, t, t)).at[..., 0].set(True)
    out_bkt, plan_b, _ = _gemm_o_parity(m_c, m_s, seed=4)
    o_tok, w, bias = _gemm_o_inputs(4, b, h, n)
    pool = 32
    dead = np.asarray(out_bkt).reshape(b, t, pool, -1)[:, 2:]
    want = np.asarray(bias).reshape(b, t, pool, -1)[:, 2:]
    np.testing.assert_array_equal(dead, want)


def test_gemm_q_guard_matches_unguarded_live_rows():
    """row_cnt guard: live slots bit-identical to the legacy full-compute
    kernel; padding slots deterministic zeros."""
    from repro.kernels.gemm_q import gemm_q_sparse_kernel
    from repro.core.symbols import active_indices
    n, d, f, block = 256, 64, 64, 32
    t = n // block
    ks = jax.random.split(jax.random.PRNGKey(5), 2)
    x = jax.random.normal(ks[0], (n, d))
    w = jax.random.normal(ks[1], (d, f))
    mask = jnp.zeros((t,), bool).at[jnp.asarray([0, 3, 5])].set(True)
    ids, cnt = active_indices(mask, t)                   # cap > live count
    guarded = gemm_q_sparse_kernel(x, w, ids, block_rows=block,
                                   row_cnt=cnt, interpret=True)
    legacy = gemm_q_sparse_kernel(x, w, ids, block_rows=block,
                                  interpret=True)        # row_cnt=None
    live = int(cnt)
    np.testing.assert_array_equal(
        np.asarray(guarded)[: live * block], np.asarray(legacy)[: live * block])
    np.testing.assert_array_equal(
        np.asarray(guarded)[live * block:],
        np.zeros_like(np.asarray(guarded)[live * block:]))


# ---------------------------------------------------------------------------
# Plan plumbing: rebuild, compaction, widen
# ---------------------------------------------------------------------------

def _engine_setup(strategy, backend, kv_buckets=3):
    from repro.core import AttnParams, init_layer_state
    key = jax.random.PRNGKey(0)
    B, H, N, dm, dh = 1, 4, 256, 64, 32
    cfg = EngineConfig(
        mask=MaskConfig(pool=32, block_q=16, block_kv=16, interval=4,
                        order=1, warmup_steps=1, tau_kv=0.15, tau_q=0.5),
        cap_q_frac=1.0, cap_kv_frac=1.0, cache_dtype=jnp.float32,
        backend=backend, strategy=strategy, kv_buckets=kv_buckets,
        interpret=True if backend == "pallas" else None)
    ks = jax.random.split(key, 8)
    p = AttnParams(
        wq=jax.random.normal(ks[0], (dm, H * dh)) * 0.05,
        wk=jax.random.normal(ks[1], (dm, H * dh)) * 0.05,
        wv=jax.random.normal(ks[2], (dm, H * dh)) * 0.05,
        wo=jax.random.normal(ks[3], (H * dh, dm)) * 0.05,
        q_scale=jnp.ones(dh), k_scale=jnp.ones(dh))
    x = jax.random.normal(ks[4], (B, N, dm))
    state = init_layer_state(B, H, N, dm, dh, cfg)
    return cfg, p, x, state, H, N


def test_plan_from_state_rebuilds_gmo_fields_bit_exact():
    cfg, p, x, state, H, N = _engine_setup("hunyuan-1.5x", "pallas")
    _, st = update_layer(p, x, state, cfg, n_text=N_TEXT, heads=H)
    assert st.plan.gmo_rows is not None
    assert st.plan.occ_hist is not None
    rebuilt = plan_from_state(st, cfg, N)
    for f in ("occ_hist", "gmo_rows", "gmo_src", "gmo_head_ids",
              "gmo_head_cnt", "head_ids", "head_cnt", "head_mask"):
        a, b = getattr(rebuilt, f), getattr(st.plan, f)
        assert a.dtype == b.dtype, f
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=f)


def test_int16_compaction_covers_gmo_and_head_ids():
    b, h, t = 1, 4, 8
    m_c = jnp.ones((b, h, t), bool)
    m_s = jnp.broadcast_to(jnp.eye(t, dtype=bool), (b, h, t, t))
    m_s = m_s.at[..., 0].set(True)
    cfg_b, _ = _cfgs()
    plan = build_dispatch_plan(m_c, m_s, cfg_b, t * 32)
    narrow = ("head_ids", "gmo_rows", "gmo_src", "gmo_head_ids")
    for f in narrow:
        assert getattr(plan, f).dtype == jnp.int16, f
    assert plan.gmo_head_cnt.dtype == jnp.int32       # a count, not an id
    assert plan.occ_hist.dtype == jnp.int32
    wide = plan.widen()
    for f in narrow:
        assert getattr(wide, f).dtype == jnp.int32, f
        np.testing.assert_array_equal(np.asarray(getattr(wide, f)),
                                      np.asarray(getattr(plan, f)))
    assert wide.widen() is wide


def test_occupancy_histogram_semantics():
    """Class i = fits width ceil(cap/2^(i+1)); dead slots excluded; the
    near-empty tail (incl. zero) lands in the last bin."""
    kv_row_cnt = jnp.asarray([[[16, 8, 4, 1, 0, 7]]], jnp.int32)
    q_cnt = jnp.asarray([[5]], jnp.int32)               # slot 5 (cnt 7) dead
    hist = occupancy_histogram(kv_row_cnt, q_cnt, 16)
    assert hist.shape == (1, OCC_BINS)
    want = np.zeros((1, OCC_BINS), np.int32)
    want[0, 0] = 1      # 16 needs full width
    want[0, 1] = 1      # 8 fits width 8 (dead 7 excluded)
    want[0, 2] = 1      # 4 fits width 4
    want[0, OCC_BINS - 1] = 2                           # 1 and 0 → last bin
    np.testing.assert_array_equal(np.asarray(hist), want)
    assert int(hist.sum()) == int(q_cnt.sum())


# ---------------------------------------------------------------------------
# Autotuner: table schema, selection, executable budget
# ---------------------------------------------------------------------------

def test_validate_table_failure_modes():
    ok = load_table()
    validate_table(ok)                                   # checked-in table
    for mutate in [
        lambda t: t.update(version=2),
        lambda t: t.pop("tiles"),
        lambda t: t["tiles"].pop("gemm_q"),
        lambda t: t["tiles"]["gemm_q"].update({"notawidth": {}}),
        lambda t: t["tiles"]["gemm_q"]["default"].update({"block_k": 500}),
        lambda t: t["bucket_model"].update({"max_clamp_frac": 2.0}),
        lambda t: t.update(strategies={"x": {"occ_hist": [-1.0]}}),
    ]:
        bad = copy.deepcopy(ok)      # the loaded table is memoized: never
        mutate(bad)                  # mutate what later callers read
        with pytest.raises(ValueError):
            validate_table(bad)


def test_select_kv_buckets_fallback_and_model():
    empty = {"version": 1, "tiles": {k: {"default": {}} for k in
                                     ("gemm_q", "gemm_o", "attention")},
             "bucket_model": {"max_clamp_frac": 0.02}, "strategies": {}}
    # Uncalibrated strategy → uniform grid, never a surprise clamp.
    assert select_kv_buckets("flashomni", empty) == 1
    assert select_kv_buckets("no-such-strategy", empty) == 1
    # All-narrow occupancy → deepest candidate admissible.
    skinny = dict(empty, strategies={"s": {"occ_hist": [0, 0, 0, 1.0]}})
    assert select_kv_buckets("s", skinny) == max(CANDIDATE_BUCKETS)
    # All-wide occupancy → any B > 1 would clamp most rows → uniform.
    wide = dict(empty, strategies={"s": {"occ_hist": [1.0]}})
    assert select_kv_buckets("s", wide) == 1
    # Cost model sanity: slot fraction halves-ish, clamp grows with B.
    assert bucket_slot_frac(1) == 1.0
    assert bucket_slot_frac(3) == pytest.approx(3 / 7)
    assert bucket_clamp_frac([1.0], 3) > bucket_clamp_frac([1.0], 2) > 0
    assert bucket_clamp_frac([0, 0, 1.0], 3) == 0.0


def test_kernel_tiles_defaults_and_width_override():
    table = {"version": 1, "tiles": {
        "gemm_q": {"default": {"block_k": 512, "block_f": 512},
                   "1024": {"block_k": 256}},
        "gemm_o": {"default": {"block_f": 512}},
        "attention": {"default": {}}},
        "bucket_model": {"max_clamp_frac": 0.02}, "strategies": {}}
    assert kernel_tiles("gemm_q", 512, table) == {"block_k": 512,
                                                  "block_f": 512}
    # Width-class override merges over the default.
    assert kernel_tiles("gemm_q", 1024, table) == {"block_k": 256,
                                                   "block_f": 512}
    assert kernel_tiles("attention", None, table) == {}


def test_auto_sentinel_resolves_statically():
    """kv_buckets = 0 resolves from (strategy, table) at spec time: a pure
    function of static config → one configuration, one executable."""
    cfg_a = EngineConfig(mask=MaskConfig(pool=32, block_q=16, block_kv=16),
                         kv_buckets=0, strategy="flashomni")
    b = cfg_a.resolved_kv_buckets()
    assert b in CANDIDATE_BUCKETS
    # Determinism: the same static config resolves to the same spec, so
    # jit caches keyed on the spec stay at one entry per configuration.
    assert cfg_a.caps(256) == cfg_a.caps(256)
    assert cfg_a.caps(256).kv_buckets == b
    # Explicit counts pass through untouched.
    cfg_3 = dataclasses.replace(cfg_a, kv_buckets=3)
    assert cfg_3.resolved_kv_buckets() == 3
    # A mesh forces uniform: seq-sharded dispatch runs per shard.
    cfg_m = dataclasses.replace(cfg_a, mesh_sp=2)
    assert cfg_m.resolved_kv_buckets() == 1

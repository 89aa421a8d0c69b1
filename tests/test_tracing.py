"""The program names its own work: ``fo.`` scopes on every op of a step,
host spans on the request path, and live-work counters of the Dispatch
kernels' grids beside the per-step density."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import scopes as S
from repro.configs.registry import get_smoke
from repro.core.engine import EngineConfig
from repro.core.masks import MaskConfig
from repro.core.plan import (bucket_geometry, bucket_grid_slots,
                             build_dispatch_plan, csr_path, csr_windows_at,
                             live_work)
from repro.core.symbols import pack_bits, unpack_bits
from repro.diffusion.pipeline import SamplerConfig, sample
from repro.kernels.flashomni_attention import walk_group
from repro.models import dit


def _ecfg(**kw):
    mask = MaskConfig(tau_q=0.5, tau_kv=0.15, interval=4, order=1,
                      degrade=0.0, block_q=16, block_kv=16, pool=16,
                      warmup_steps=2)
    return EngineConfig(mask=mask, cache_dtype=jnp.float32, cap_q_frac=1.0,
                        cap_kv_frac=1.0, **kw)


@pytest.fixture(scope="module")
def model():
    cfg = get_smoke("flux-mmdit")
    params = dit.init_params(cfg, jax.random.PRNGKey(0))
    key = jax.random.PRNGKey(1)
    text = jax.random.normal(key, (1, cfg.n_text_tokens, cfg.d_model))
    x0 = jax.random.normal(jax.random.fold_in(key, 1), (1, 64, cfg.patch_dim))
    return cfg, params, text, x0


def _sample(model, ecfg, **kw):
    cfg, params, text, x0 = model
    return sample(params, cfg, ecfg, text_emb=text, x0=x0,
                  scfg=SamplerConfig(num_steps=8), **kw)


# ---------------------------------------------------------------------------
# Device scopes
# ---------------------------------------------------------------------------

# Ops that do no work of their own, and what lax.scan adds around the
# layer body (slicing the stacked weights and states, stacking the new
# states, the layer counter and its test): these carry the step mode and
# no part.
_NO_WORK = {"parameter", "constant", "get-tuple-element", "tuple", "bitcast",
            "while", "conditional", "call"}
_LOOP = {"dynamic_slice", "dynamic_update_slice", "add", "lt"}


def _step_ops(text: str):
    """(opcode, op_name) of every executed instruction under a step branch:
    top-level instructions of the computations that are not fused."""
    fused = set(re.findall(r"calls=%([\w.\-]+)", text))
    comp, out = None, []
    for line in text.splitlines():
        head = re.match(r"^(?:ENTRY )?%?([\w.\-]+) .*\{$", line)
        if head:
            comp = head.group(1)
            continue
        m = re.match(r"^\s*(?:ROOT )?%[\w.\-]+ = .*?\s([a-z][\w\-]*)\(", line)
        op_name = re.search(r'op_name="([^"]*)"', line)
        if m and op_name and comp not in fused \
                and "/branch_" in op_name.group(1):
            out.append((m.group(1), op_name.group(1)))
    return out


def test_every_step_op_is_under_one_mode_and_one_part(model):
    stats: dict = {}
    _sample(model, _ecfg(backend="xla"), trace=[], stats=stats)
    ops = _step_ops(stats["lower"]().compile().as_text())
    by_mode: dict = {}
    for opcode, op_name in ops:
        segs = op_name.split("/")
        modes = [s for s in segs if s in S.MODES]
        parts = [s for s in segs if s.startswith("fo.") and s not in S.MODES]
        assert len(modes) == 1, op_name
        assert len(parts) <= 1, op_name
        assert all(p[3:] in S.PARTS for p in parts), op_name
        if opcode in _NO_WORK or (not parts and segs[-1] in _LOOP
                                  and segs[-2] in ("body", "cond")):
            continue
        n, bare = by_mode.get(modes[0], (0, 0))
        by_mode[modes[0]] = (n + 1, bare + (not parts))
    assert set(by_mode) == {"fo.dense", "fo.update", "fo.dispatch"}
    for mode, (n, bare) in by_mode.items():
        assert bare <= 0.03 * n, (mode, bare, n)
    # Each mode runs the parts it should, and only those.
    parts = {m: {S.scope_of(o)["part"] for _, o in ops
                 if S.scope_of(o)["mode"] == m[3:]} for m in by_mode}
    assert {"symbols", "plan"} <= parts["fo.update"]
    assert not {"symbols", "plan"} & parts["fo.dispatch"]
    for m in by_mode:
        assert {"qkv", "attention", "o_proj", "mlp", "io"} <= parts[m]


# ---------------------------------------------------------------------------
# Live-work counters
# ---------------------------------------------------------------------------

def _masks(seed: int, b: int, h: int, t: int):
    rng = np.random.default_rng(seed)
    m_c = rng.random((b, h, t)) < 0.6
    m_s = rng.random((b, h, t, t)) < 0.3
    m_s[..., np.arange(t), np.arange(t)] = True      # every row attends
    # Through the packed symbols, as a Dispatch step receives them.
    s_c, s_s = pack_bits(jnp.asarray(m_c)), pack_bits(
        jnp.asarray(m_s.reshape(b, h, t * t)))
    return (unpack_bits(s_c, t),
            unpack_bits(s_s, t * t).reshape(b, h, t, t))


def _recount(m_c, m_s, plan, spec, heads):
    """The counters recounted in NumPy: each tile, row and head a kernel
    would compute that the masks hold live, and the slots its grid walks
    (the uniform CSR walk launches only the live tiles, ``ceil(n / G)``
    updates of ``G`` blocks a row of ``n``)."""
    m_c, m_s = np.asarray(m_c), np.asarray(m_s)
    p = jax.tree.map(np.asarray, plan.widen())
    b, cr = p.row_ids.shape
    rows = heads_live = tiles = 0
    for bi in range(b):
        for c in range(p.row_cnt[bi]):
            r = p.row_ids[bi, c]
            rows += bool(m_c[bi, :, r].any())
            heads_live += sum(bool(m_c[bi, hh, r])
                              for hh in p.head_ids[bi, c, :p.head_cnt[bi, c]])
        for hh in range(heads):
            for c in range(p.q_cnt[bi, hh]):
                i = p.q_ids[bi, hh, c]                 # block granularity
                tiles += sum(bool(m_c[bi, hh, i] and m_s[bi, hh, i, j])
                             for j in p.kv_row_ids[bi, hh, c,
                                                    :p.kv_row_cnt[bi, hh, c]])
    if spec.kv_buckets > 1:
        csr_grid = b * bucket_grid_slots(
            bucket_geometry(spec.cap_q, spec.cap_kv, heads, spec.kv_buckets))
        gmo_grid = b * bucket_grid_slots(
            bucket_geometry(cr, heads, 1, spec.kv_buckets))
        groups = csr_grid
    else:
        counts = [int(p.kv_row_cnt[bi, hh, c]) for bi in range(b)
                  for hh in range(heads) for c in range(p.q_cnt[bi, hh])]
        csr_grid = sum(counts)
        g = walk_group(p.kv_row_ids.shape[-1])
        groups = g * sum(-(-n // g) for n in counts)
        gmo_grid = b * cr * heads
    return {"gemm_q_rows": (rows, b * cr), "csr_tiles": (tiles, csr_grid),
            "csr_groups": (tiles, groups),
            "gemm_o_heads": (heads_live, gmo_grid)}


@pytest.mark.parametrize("kv_buckets", [1, 3])
def test_live_work_matches_a_recount_from_the_masks(kv_buckets):
    b, h, t = 2, 3, 8
    n_tokens = 16 * t                  # pool = block = 16: one block per row
    cfg = _ecfg(kv_buckets=kv_buckets)
    spec = cfg.caps(n_tokens)
    layers = []
    for layer in range(2):
        m_c, m_s = _masks(layer, b, h, t)
        plan = build_dispatch_plan(m_c, m_s, cfg, n_tokens)
        layers.append((m_c, m_s, plan))
        assert (plan.bkt_kv_cnt is not None) == (kv_buckets > 1)
        want = _recount(m_c, m_s, plan, spec, h)
        got = {k: (int(v), n) for k, (v, n) in live_work(plan).items()}
        assert got == want
        if kv_buckets == 1:
            # No capacity truncates here: every live tile, row and (row,
            # head) pair of the masks is computed.
            m_c_np, m_s_np = np.asarray(m_c), np.asarray(m_s)
            assert got["gemm_q_rows"][0] == m_c_np.any(axis=1).sum()
            assert got["gemm_o_heads"][0] == m_c_np.sum()
            assert got["csr_tiles"][0] == (m_s_np & m_c_np[..., None]).sum()
        for live, launched in got.values():
            assert 0 < live <= launched
    # Over stacked layers, the counters are the layers' sums.
    stacked = jax.tree.map(lambda *a: jnp.stack(a), *(p for *_, p in layers))
    each = [live_work(p) for *_, p in layers]
    for k, (live, launched) in live_work(stacked).items():
        assert int(live) == sum(int(w[k][0]) for w in each)
        assert launched == sum(w[k][1] for w in each)


@pytest.mark.parametrize("mesh_sp", [1, 2])
def test_resident_walk_launches_only_live_tiles(mesh_sp):
    """The CSR walk has no grid step per tile: resident or cut into
    windows, it launches the live rows' tiles and nothing else, on one
    device and per shard."""
    b, h, t = 2, 3, 8
    m_c, m_s = _masks(7, b, h, t)
    cfg = _ecfg(mesh_sp=mesh_sp)
    plan = build_dispatch_plan(m_c, m_s, cfg, 16 * t)
    work = live_work(plan)
    assert 0 < int(work["csr_tiles"][0]) == int(work["csr_tiles"][1])
    if mesh_sp == 1:
        want = _recount(m_c, m_s, plan, cfg.caps(16 * t), h)
        assert int(work["csr_tiles"][1]) == want["csr_tiles"][1]


def test_csr_path_follows_the_shapes():
    """flux width and hunyuan length are resident (bf16 and f32 K/V),
    twice hunyuan's length walks windows, the sequence-sharded mesh reads
    its per-shard buffer, and kv_buckets > 1 takes the bucketed grid."""
    from repro.configs.registry import get_config
    flux = get_config("flux-mmdit")
    ecfg = EngineConfig()
    assert csr_path(ecfg, 4608, flux.hd, jnp.bfloat16) == "resident"
    assert csr_path(ecfg, 4608, flux.hd, jnp.float32) == "resident"
    assert csr_path(ecfg, 33_024, flux.hd, jnp.bfloat16) == "resident"
    assert csr_path(ecfg, 66_048, flux.hd, jnp.bfloat16) == "windowed"
    assert csr_windows_at(ecfg, 66_048, flux.hd, jnp.bfloat16) == 2
    assert csr_path(EngineConfig(mesh_sp=4), 4608, flux.hd,
                    jnp.bfloat16) == "resident"
    assert csr_path(EngineConfig(mesh_sp=4), 33_024, flux.hd,
                    jnp.bfloat16) == "resident"
    assert csr_path(EngineConfig(mesh_sp=4), 264_192, flux.hd,
                    jnp.bfloat16) == "windowed"
    assert csr_path(EngineConfig(kv_buckets=3), 4608, flux.hd,
                    jnp.bfloat16) == "bucketed"


def test_live_work_on_a_plan_sharded_mesh():
    """On the (1, 2) engine mesh the CSR kernel runs per shard over the
    shard-local lists: the same live tiles, the per-shard grid."""
    b, h, t = 2, 3, 8
    m_c, m_s = _masks(5, b, h, t)
    one = build_dispatch_plan(m_c, m_s, _ecfg(), 16 * t)
    mesh = build_dispatch_plan(m_c, m_s, _ecfg(mesh_sp=2), 16 * t)
    work, work1 = live_work(mesh), live_work(one)
    assert int(work["csr_tiles"][0]) == int(work1["csr_tiles"][0]) \
        == work["csr_tiles"][1]
    for k in ("gemm_q_rows", "gemm_o_heads"):
        assert (int(work[k][0]), work[k][1]) == (int(work1[k][0]), work1[k][1])


@pytest.mark.parametrize("mesh_sp", [1, 2])
def test_csr_groups_count_the_walks_updates(mesh_sp):
    """Four live rows of 16, 9, 8 and 1 KV blocks (34 tiles), two on each
    shard of the (1, 2) mesh: ``ceil(n / G)`` updates a row, counted as
    ``G`` tiles each, by hand for the walk groups tried on the chip."""
    t, rows, counts = 16, (0, 5, 9, 14), (16, 9, 8, 1)
    m_c = np.zeros((1, 1, t), bool)
    m_s = np.zeros((1, 1, t, t), bool)
    for r, n in zip(rows, counts):
        m_c[0, 0, r] = True
        m_s[0, 0, r, t - n:] = True
    plan = build_dispatch_plan(jnp.asarray(m_c), jnp.asarray(m_s),
                               _ecfg(mesh_sp=mesh_sp), 16 * t)
    if mesh_sp == 1:
        assert np.asarray(plan.kv_row_cnt)[0, 0, :4].tolist() == [16, 9, 8, 1]
    else:
        assert sorted(np.asarray(plan.shd_kv_row_cnt)[
            np.arange(plan.shd_kv_row_cnt.shape[-1])
            < np.asarray(plan.shd_q_cnt)[..., None]].tolist()) == [1, 8, 9, 16]
    g = walk_group(t)
    by_hand = {4: 4 * (4 + 3 + 2 + 1), 8: 8 * (2 + 2 + 1 + 1),
               16: 16 * (1 + 1 + 1 + 1)}
    live, launched = live_work(plan)["csr_groups"]
    assert (int(live), int(launched)) == (34, by_hand[g])


def test_step_counters_ride_the_trace_and_leave_outputs_alone(model):
    ecfg = _ecfg(backend="xla")
    trace: list = []
    plain = _sample(model, ecfg)
    traced = _sample(model, ecfg, trace=trace)
    np.testing.assert_array_equal(np.asarray(plain), np.asarray(traced))
    assert len(trace) == 8
    for st in trace:
        assert {"step", "kind", "density", "pair_sparsity"} <= set(st)
        assert set(st["live"]) == set(st["grid"]) == {
            "gemm_q_rows", "csr_tiles", "csr_groups", "gemm_o_heads"}
        for k, live in st["live"].items():
            assert 0 <= live <= st["grid"][k]
    dispatch = [st for st in trace if st["kind"] == "dispatch"]
    assert dispatch and all(st["live"]["csr_tiles"] > 0 for st in dispatch)
    # At this size K and V fit VMEM: the CSR kernel walks only live tiles.
    assert {st["csr_path"] for st in trace} == {"resident"}
    for st in dispatch:
        assert st["live"]["csr_tiles"] == st["grid"]["csr_tiles"]
    # With no capacity in the way, the (row, head) pairs GEMM-O reduces
    # are the live share the density counts.
    for st in dispatch:
        assert st["live"]["gemm_o_heads"] / st["grid"]["gemm_o_heads"] == \
            pytest.approx(st["density"], abs=1e-6)


# ---------------------------------------------------------------------------
# Host spans
# ---------------------------------------------------------------------------

def test_request_path_spans_name_the_host_work(model, tmp_path):
    from repro.launch.batching import Request, run_sequential
    cfg, params, text, x0 = model
    ecfg = _ecfg(backend="xla", strategy="cache-all")   # a sampler of its own
    reqs = [Request(rid=i, x0=x0, text_emb=text, num_steps=3)
            for i in (7, 8)]
    jax.profiler.start_trace(str(tmp_path))
    try:
        out = run_sequential(params, cfg, ecfg, reqs)
    finally:
        jax.profiler.stop_trace()
    assert set(out) == {7, 8}
    spans = S.load_spans(str(tmp_path))
    names = [n for n, *_ in spans]
    for name in ("fo.request", "fo.wait", "fo.fetch", "fo.states",
                 "fo.schedule", "fo.launch", "fo.metrics"):
        assert names.count(name) == 2, name
    requests = sorted((s, d, a) for n, s, d, a in spans if n == "fo.request")
    assert [a["rid"] for *_, a in requests] == [7, 8]
    # The first request compiled its sampler; the second reused it.
    launches = sorted((s, a) for n, s, _, a in spans if n == "fo.launch")
    assert [bool(a["compiled"]) for _, a in launches] == [True, False]
    for n, s, d, _ in spans:
        if n != "fo.request":
            assert any(rs <= s and s + d <= rs + rd
                       for rs, rd, _ in requests), n

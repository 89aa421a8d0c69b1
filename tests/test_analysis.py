"""Engine invariant analyzer tests (ISSUE 9).

Two sides of the acceptance criterion:

* adversarial fixtures every pass MUST flag — an injected ``lax.sort``
  in a dispatch-shaped fn, a hand-mutated plan violating fold-back
  (counts past widths, out-of-range ids), a plan leaf ``widen()`` does
  not cover, an ``id()``-keyed module cache, jit under a traced body;
* green runs on the REAL engine: Dispatch purity for every registered
  strategy × backend, the structural plan validator over real plans
  (uniform + bucketed + mesh-partitioned), the serving-tick promotion
  and executable-budget passes, and the source lint over ``src/``.

Mesh-device-bound combos (CollectiveBudget, mesh DispatchPurity) run in
the forced-8-device CI step via ``python -m repro.analysis``; here they
skip gracefully on one device.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

jax.config.update("jax_platforms", "cpu")

from repro.analysis import AnalysisContext
from repro.analysis.jaxpr_walk import (eqn_count, index_decode_eqns,
                                       primitive_counts)
from repro.analysis.passes import (_B, _DH, _DM, _H, _N, ExecutableBudget,
                                   PromotionCheck, _engine_cfg, _params,
                                   _trace_pair)
from repro.analysis.plan_check import (PlanInvariantError, check_plan,
                                       validate_plan)
from repro.analysis.source_lint import lint_source, lint_sources
from repro.core.engine import init_layer_state, update_layer
from repro.core.strategy import available_strategies


def _ctx():
    return AnalysisContext(src_root="src")


@pytest.fixture(scope="module")
def real_plan():
    """One concrete bucketed plan off the real Update path."""
    cfg = _engine_cfg(kv_buckets=3)
    x = jax.random.normal(jax.random.PRNGKey(0), (_B, _N, _DM)) * 0.3
    st0 = init_layer_state(_B, _H, _N, _DM, _DH, cfg)
    _, st = update_layer(_params(), x, st0, cfg, n_text=32, heads=_H,
                         step_idx=2, num_steps=8)
    return cfg, st.plan


# ---------------------------------------------------------------------------
# jaxpr walker
# ---------------------------------------------------------------------------

def test_walker_recurses_into_nested_sub_jaxprs():
    """A sort hidden under jit-inside-scan is invisible to jaxpr-TEXT
    grep at the top level but must be found by the walker."""
    @jax.jit
    def hidden(x):
        def body(c, row):
            return c, jax.lax.sort(row)
        _, ys = jax.lax.scan(body, 0, x)
        return ys

    jx = jax.make_jaxpr(hidden)(jnp.ones((4, 8)))
    hits = index_decode_eqns(jx)
    assert len(hits) == 1
    path, eqn = hits[0]
    assert eqn.primitive.name == "sort"
    assert "scan" in path            # found inside the scan body
    counts = primitive_counts(jx)
    assert counts["sort"] == 1 and counts["scan"] == 1


def test_walker_flags_uint8_unpack_signature():
    """unpack_bits has no named primitive — the walker recognizes its
    uint8 bit-shift signature instead."""
    from repro.core.symbols import unpack_bits
    jx = jax.make_jaxpr(lambda s: unpack_bits(s, 16))(
        jnp.zeros((2, 2), jnp.uint8))
    assert index_decode_eqns(jx), "uint8 unpack signature not detected"


def test_eqn_count_modes():
    def f(x):
        def body(c, v):
            return c + v, v * 2
        return jax.lax.scan(body, 0.0, x)

    jx = jax.make_jaxpr(f)(jnp.ones(8))
    assert eqn_count(jx) == 1                      # the scan itself
    assert eqn_count(jx, recursive=True) > 1       # plus its body


# ---------------------------------------------------------------------------
# adversarial fixtures (each MUST be flagged)
# ---------------------------------------------------------------------------

def test_injected_sort_in_dispatch_fn_is_flagged():
    def dispatch_like(x, ids):
        return jnp.take(x, jax.lax.sort(ids), axis=0)

    jx = jax.make_jaxpr(dispatch_like)(jnp.ones((8, 4)),
                                       jnp.arange(8, dtype=jnp.int32))
    assert {e.primitive.name for _, e in index_decode_eqns(jx)} == {"sort"}


def test_foldback_violating_plan_is_flagged(real_plan):
    cfg, plan = real_plan
    mutated = plan._replace(
        bkt_kv_cnt=plan.bkt_kv_cnt + 7,                # counts > widths
        kv_row_ids=jnp.full_like(plan.kv_row_ids, 2 ** 14))  # ids OOR
    bad = check_plan(mutated, cfg, _N)
    assert any("outside [0" in m for m in bad)
    assert any("fold-back" in m for m in bad)
    with pytest.raises(PlanInvariantError):
        validate_plan(mutated, cfg, _N)


def test_widen_uncovered_field_is_flagged(real_plan):
    cfg, plan = real_plan
    bad = check_plan(plan._replace(q_cnt=plan.q_cnt.astype(jnp.int16)),
                     cfg, _N)
    assert any("stayed int16" in m for m in bad)


def test_occ_hist_mismatch_is_flagged(real_plan):
    cfg, plan = real_plan
    bad = check_plan(
        plan._replace(occ_hist=plan.occ_hist.at[..., 0].add(1)), cfg, _N)
    assert any("occ_hist" in m for m in bad)


def test_id_keyed_cache_is_flagged():
    src = ("_PLAN_CACHE = {}\n"
           "def lookup(spec):\n"
           "    key = id(spec)\n"
           "    if key not in _PLAN_CACHE:\n"
           "        _PLAN_CACHE[key] = spec\n"
           "    return _PLAN_CACHE[key]\n")
    rules = {r for _, _, r, _ in lint_source(src)}
    assert "id-keyed-cache" in rules
    assert "module-dict-cache" in rules   # unbounded dict cache too


def test_transient_local_id_dict_is_not_flagged():
    """schedule.strategy_table's pattern: id() keys into a TRANSIENT
    local dict over pinned objects is legal — no cache involved."""
    src = ("def table(specs):\n"
           "    by_spec = {}\n"
           "    for s in specs:\n"
           "        by_spec[id(s)] = resolve(s)\n"
           "    return by_spec\n")
    assert lint_source(src) == []


def test_jit_in_traced_body_is_flagged():
    src = ("import jax\n"
           "def outer(xs):\n"
           "    def body(c, x):\n"
           "        f = jax.jit(lambda v: v * 2)\n"
           "        return c, f(x)\n"
           "    return jax.lax.scan(body, 0, xs)\n")
    assert {r for _, _, r, _ in lint_source(src)} == {"jit-in-traced-body"}


# ---------------------------------------------------------------------------
# green runs on the real engine
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", ["xla", "pallas"])
@pytest.mark.parametrize("strategy", available_strategies())
def test_dispatch_purity_per_strategy_backend(strategy, backend):
    """Every registered strategy × backend: Dispatch jaxpr decode-free,
    Update jaxpr the positive control (kv_buckets=3 exercises the
    bucketed layouts on both backends)."""
    cfg = _engine_cfg(strategy=strategy, backend=backend, kv_buckets=3,
                      **(dict(interpret=True) if backend == "pallas"
                         else {}))
    upd, disp = _trace_pair(cfg)
    hits = index_decode_eqns(disp)
    assert not hits, (
        f"{strategy}/{backend}: dispatch rebuilds indices: "
        + ", ".join(e.primitive.name for _, e in hits))
    assert index_decode_eqns(upd), \
        f"{strategy}/{backend}: vacuous walker — no decode in Update"


@pytest.mark.parametrize("strategy", available_strategies())
def test_plan_validator_green_per_strategy(strategy):
    """Real plans (bucketed, plus the mesh partition for the default
    strategy) satisfy every structural invariant."""
    cfg = _engine_cfg(strategy=strategy, kv_buckets=3)
    x = jax.random.normal(jax.random.PRNGKey(1), (_B, _N, _DM)) * 0.3
    st0 = init_layer_state(_B, _H, _N, _DM, _DH, cfg)
    _, st = update_layer(_params(), x, st0, cfg, n_text=32, heads=_H,
                         step_idx=2, num_steps=8)
    assert check_plan(st.plan, cfg, _N) == []


def test_plan_validator_green_on_mesh_partition(monkeypatch):
    """The shd_* partition checks run on ONE device (partition_plan is
    pure jnp at Update time; Update's attention runs unsharded here, as
    its per-shard form needs the mesh's devices)."""
    from repro.core.backend import MeshBackend
    monkeypatch.setattr(MeshBackend, "dense_attention",
                        lambda self, q, k, v: self.inner.dense_attention(
                            q, k, v))
    cfg = _engine_cfg(kv_buckets=1, mesh_dp=1, mesh_sp=2)
    x = jax.random.normal(jax.random.PRNGKey(2), (_B, _N, _DM)) * 0.3
    st0 = init_layer_state(_B, _H, _N, _DM, _DH, cfg)
    _, st = update_layer(_params(), x, st0, cfg, n_text=32, heads=_H,
                         step_idx=2, num_steps=8)
    assert st.plan.shd_q_ids is not None
    assert check_plan(st.plan, cfg, _N) == []


def test_plan_validator_tolerates_stacked_axes(real_plan):
    """Layer/lane stacking adds leading axes; the checker folds them."""
    cfg, plan = real_plan
    stacked = jax.tree.map(
        lambda a: jnp.broadcast_to(a, (2, *a.shape)), plan)
    assert check_plan(stacked, cfg, _N) == []


def test_promotion_and_budget_passes_green():
    ctx = _ctx()
    assert PromotionCheck().run(ctx) == []
    assert ExecutableBudget().run(ctx) == []


def test_source_lint_green_on_repo():
    assert lint_sources("src") == []


def test_sweep_configs_covers_full_matrix():
    """The analyzer's sweep enumerates every registered strategy ×
    backend × kv_buckets ∈ {1,3} × {single, mesh} combo (mesh combos
    carry a skip note on hosts without 2 devices rather than vanishing
    silently)."""
    from repro.analysis.passes import sweep_configs
    combos = list(sweep_configs())
    strategies = set(available_strategies())
    assert len(combos) == len(strategies) * 2 * 2 * 2
    live = [(label, cfg) for label, cfg, skip in combos if skip is None]
    assert {c.strategy for _, c in live} == strategies
    assert {c.backend for _, c in live} == {"xla", "pallas"}
    assert {c.kv_buckets for _, c in live} == {1, 3}
    # skipped combos (mesh on a small host) must say so, never vanish
    for label, cfg, skip in combos:
        if skip is not None:
            assert cfg is None and "mesh" in label and "devices" in skip
    # the single-device half of the grid always runs
    assert len(live) >= len(strategies) * 2 * 2


# ---------------------------------------------------------------------------
# satellite 2: PR 7/8 field coverage regression (widen + specs + rebuild)
# ---------------------------------------------------------------------------

def test_pr78_fields_covered_by_widen_and_specs():
    """Every gmo_*/shd_*/occ_hist field from PRs 7–8 is wired through
    widen() (id fields), engine_state_specs, and the build path — the
    static lint finds zero coverage gaps, and the live widen() of a real
    plan leaves no int16 leaf."""
    import ast
    from pathlib import Path

    from repro.analysis.source_lint import is_id_field, plan_fields
    tree = ast.parse(Path("src/repro/core/plan.py").read_text())
    fields = plan_fields(tree)
    pr78 = [f for f in fields
            if f.startswith(("gmo_", "shd_")) or f == "occ_hist"]
    assert len(pr78) >= 16          # 4 gmo + 11 shd + occ_hist
    hits = [h for h in lint_sources("src") if h[2].startswith("plan-")]
    assert hits == []
    # and the id-field convention actually captures the PR 7/8 id lists
    assert {f for f in pr78 if is_id_field(f)} >= {
        "gmo_rows", "gmo_src", "gmo_head_ids", "shd_q_ids", "shd_q_src",
        "shd_q_slots", "shd_kv_ids", "shd_kv_row_ids", "shd_gather_idx",
        "shd_send_ids"}


def test_widen_roundtrip_complete_on_real_plans(real_plan):
    cfg, plan = real_plan
    wide = plan.widen()
    for name, leaf in zip(wide._fields, wide):
        if leaf is not None and hasattr(leaf, "dtype"):
            assert leaf.dtype != jnp.int16, f"{name} stayed int16"
    # idempotent
    again = wide.widen()
    for a, b in zip(jax.tree.leaves(wide), jax.tree.leaves(again)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# live validation hook
# ---------------------------------------------------------------------------

def test_validate_plans_hook_fires_and_passes(monkeypatch):
    """EngineConfig.validate_plans=True routes every plan build through
    the host-side checker (and real plans pass it)."""
    from repro.analysis import plan_check
    calls = []
    real = plan_check.hook_validate
    monkeypatch.setattr(plan_check, "hook_validate",
                        lambda p, cfg, n: calls.append(1) or real(p, cfg, n))
    cfg = dataclasses.replace(_engine_cfg(kv_buckets=3),
                              validate_plans=True)
    x = jax.random.normal(jax.random.PRNGKey(5), (_B, _N, _DM)) * 0.3
    st0 = init_layer_state(_B, _H, _N, _DM, _DH, cfg)
    _, st = update_layer(_params(), x, st0, cfg, n_text=32, heads=_H,
                         step_idx=2, num_steps=8)
    jax.block_until_ready(st.plan.q_cnt)
    assert calls, "validate_plans=True did not reach the host checker"


def test_validate_plans_env_gate(monkeypatch):
    from repro.analysis.plan_check import validation_enabled
    cfg = _engine_cfg()
    monkeypatch.delenv("REPRO_VALIDATE_PLANS", raising=False)
    assert not validation_enabled(cfg)
    monkeypatch.setenv("REPRO_VALIDATE_PLANS", "1")
    assert validation_enabled(cfg)
    monkeypatch.setenv("REPRO_VALIDATE_PLANS", "0")
    assert not validation_enabled(cfg)
    assert validation_enabled(dataclasses.replace(cfg,
                                                  validate_plans=True))


def test_collective_budget_green_or_noted_skip():
    """Zero findings either way: on a single-device host the pass
    records a skip note instead of silently vanishing; with >= 2
    devices (CI's forced-8-device step) it verifies the a2a budget."""
    from repro.analysis.passes import CollectiveBudget, mesh_capacity
    ctx = _ctx()
    assert CollectiveBudget().run(ctx) == []
    if mesh_capacity() < 2:
        assert ctx.notes, "1-device skip must leave a note"


# ---------------------------------------------------------------------------
# ISSUE 10: static cost model
# ---------------------------------------------------------------------------

from repro.analysis.cost_model import (CostEstimate, aval_bytes,  # noqa: E402
                                       cost_of_jaxpr, peak_bytes_of)


def test_cost_model_dot_general_exact():
    m, k, n = 48, 96, 32
    jx = jax.make_jaxpr(lambda a, b: a @ b)(
        jax.ShapeDtypeStruct((m, k), jnp.float32),
        jax.ShapeDtypeStruct((k, n), jnp.float32))
    c = cost_of_jaxpr(jx)
    assert c.flops == 2.0 * m * n * k
    assert c.hbm_bytes == 4.0 * (m * k + k * n + m * n)
    assert not c.inexact and not c.coll_payload


def test_cost_model_matches_xla_on_dense_gemm_and_attention():
    """The headline cross-check: static count vs XLA cost_analysis."""
    from repro.core.attention import dense_attention

    def xla_flops(fn, *args):
        c = jax.jit(fn).lower(*args).compile().cost_analysis()
        return float(c.get("flops", 0.0))

    gemm = lambda a, b: jnp.einsum("bnd,df->bnf", a, b)
    a = jnp.ones((1, 128, 64))
    b = jnp.ones((64, 32))
    assert cost_of_jaxpr(jax.make_jaxpr(gemm)(a, b)).flops == \
        xla_flops(gemm, a, b)

    q = jax.random.normal(jax.random.PRNGKey(0), (2, 128, 16))
    att = lambda q: dense_attention(q, q, q)
    static = cost_of_jaxpr(jax.make_jaxpr(att)(q)).flops
    measured = xla_flops(att, q)
    assert abs(static - measured) / measured < 0.05


def test_cost_model_scan_multiplies_by_trip_count():
    def body_cost(xs):
        def step(c, x):
            return c + (x @ x), None
        out, _ = jax.lax.scan(step, jnp.zeros((16, 16)), xs)
        return out

    c8 = cost_of_jaxpr(jax.make_jaxpr(body_cost)(jnp.ones((8, 16, 16))))
    c16 = cost_of_jaxpr(jax.make_jaxpr(body_cost)(jnp.ones((16, 16, 16))))
    # matmul flops dominate and scale exactly with the trip count
    assert c16.flops == pytest.approx(2 * c8.flops, rel=1e-6)


def test_cost_model_gather_bills_touched_bytes_not_operand():
    """A plan-capacity gather over a big KV buffer must cost what it
    moves — the whole point of the T_kv-independence certificate."""
    big = jax.ShapeDtypeStruct((4096, 64), jnp.float32)   # 1 MB operand
    ids = jnp.arange(4, dtype=jnp.int32)
    c = cost_of_jaxpr(jax.make_jaxpr(
        lambda x, i: jnp.take(x, i, axis=0))(big, ids))
    assert c.hbm_bytes < 0.01 * aval_bytes(big)


def test_cost_model_while_marks_inexact():
    def f(x):
        return jax.lax.while_loop(lambda v: v[0] < 10.0,
                                  lambda v: v * 1.5, x)

    assert cost_of_jaxpr(jax.make_jaxpr(f)(jnp.ones(4))).inexact


def test_peak_bytes_sees_liveness_not_total_allocation():
    """A chain of sequential temporaries peaks at a few buffers, far
    below the sum of every intermediate."""
    def chain(x):
        for _ in range(16):
            x = x + 1.0
        return x

    jx = jax.make_jaxpr(chain)(jnp.ones((256, 256)))
    buf = 256 * 256 * 4
    peak = peak_bytes_of(jx)
    assert buf <= peak <= 4 * buf        # not 17 * buf


def test_peak_bytes_counts_concurrently_live_buffers():
    def wide(x):
        a, b, c = x + 1.0, x * 2.0, x - 3.0
        return a + b + c                 # all three live together

    jx = jax.make_jaxpr(wide)(jnp.ones((128, 128)))
    assert peak_bytes_of(jx) >= 3 * 128 * 128 * 4


# ---------------------------------------------------------------------------
# ISSUE 10: cost passes — adversarial fixtures (each MUST be flagged)
# ---------------------------------------------------------------------------

from repro.analysis.cost_passes import (COST_PASSES,  # noqa: E402
                                        CollectiveBytesBudget,
                                        DispatchCostScaling, MemoryFootprint,
                                        PEAK_BUDGETS, UpdateAmortization,
                                        _dense_reference_cost, _matched,
                                        _token_reference_slope, _update_cost,
                                        KAPPA_TOKEN, KAPPA_TOKEN_BYTES,
                                        amortization_findings,
                                        collective_findings,
                                        footprint_findings,
                                        token_scaling_findings)


def test_dense_tkv_einsum_in_dispatch_is_flagged():
    """A dispatch body with an O(T_kv^2) score matrix fails the
    matched-capacity linearity certificate."""
    def dispatch_like(x, k):
        live = jnp.take(x, jnp.arange(32), axis=0)      # plan-capacity work
        return live.sum() + jnp.einsum("nd,md->nm", x, k).sum()

    ns = (128, 256, 384)
    costs = [cost_of_jaxpr(jax.make_jaxpr(dispatch_like)(
        jax.ShapeDtypeStruct((n, 16), jnp.float32),
        jax.ShapeDtypeStruct((n, 16), jnp.float32))) for n in ns]
    ref_f, ref_b = _token_reference_slope()
    findings = token_scaling_findings(
        "cost-dispatch-scaling", "fixture", costs, ns,
        budget_flops=KAPPA_TOKEN * ref_f,
        budget_bytes=KAPPA_TOKEN_BYTES * ref_b)
    assert any(f.rule == "tkv-superlinear" for f in findings)


def test_affine_dispatch_cost_passes_scaling_certificate():
    """The positive control for the fixture above: plan-capacity-only
    work (affine in n under the per-token budget) produces no findings."""
    def clean(x):
        live = jnp.take(x, jnp.arange(32), axis=0)
        return live.sum() + x.sum()

    ns = (128, 256, 384)
    costs = [cost_of_jaxpr(jax.make_jaxpr(clean)(
        jax.ShapeDtypeStruct((n, 16), jnp.float32))) for n in ns]
    ref_f, ref_b = _token_reference_slope()
    assert token_scaling_findings(
        "cost-dispatch-scaling", "clean", costs, ns,
        budget_flops=KAPPA_TOKEN * ref_f,
        budget_bytes=KAPPA_TOKEN_BYTES * ref_b) == []


def test_full_kv_allgather_is_flagged():
    """A mesh dispatch shipping the whole KV (all_gather, no pair_cap
    a2a) violates every line of the collective certificate — built from
    a synthetic estimate so the test runs on one device."""
    smuggled = CostEstimate(coll_payload={"all_gather": 65536.0},
                            coll_count={"all_gather": 2})
    findings = collective_findings("cost-collective-bytes", "fixture",
                                   smuggled, expected_payload=24576.0,
                                   dense_payload=65536.0)
    rules = {f.rule for f in findings}
    assert {"a2a-count", "pair-cap-formula",
            "no-extra-collectives"} <= rules


def test_rebuild_every_dispatch_is_flagged():
    """dispatch cost := update cost models an engine that rebuilds the
    plan every step — the amortization line must fail, against XLA's
    dense step on both backends."""
    for kw in (dict(backend="xla"), dict(backend="pallas", interpret=True)):
        cfg = _matched(_engine_cfg(kv_buckets=1, **kw), 2, 2, _N)
        u = _update_cost(cfg, _N)
        findings = amortization_findings(
            "cost-update-amortization", "fixture", u, u,
            _dense_reference_cost(_N), cfg.mask.interval)
        assert any(f.rule == "interval-amortization" for f in findings), kw


def test_kernel_declared_cost_replaces_its_body_walk():
    """A ``pallas_call`` that declares its cost is costed by it (one FLOP
    per transcendental, the HBM bytes it declares), not by its body times
    the grid; the dense attention kernel declares its matmul FLOPs, one
    ``exp`` per score, Q and O once and K/V once per query tile."""
    from jax.experimental import pallas as pl
    from repro.analysis.cost_model import cost_of_jaxpr
    from repro.kernels.dense_attention import (dense_tiles,
                                               flashomni_dense_attention)

    def double(x_ref, o_ref):
        o_ref[...] = x_ref[...] * 2.0

    def call(x, ce):
        spec = pl.BlockSpec((8, 128), lambda i: (i, 0))
        return pl.pallas_call(
            double, grid=(4,), in_specs=[spec], out_specs=spec,
            out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
            cost_estimate=ce, interpret=True)(x)

    x = jax.ShapeDtypeStruct((32, 128), jnp.float32)
    walked = cost_of_jaxpr(jax.make_jaxpr(lambda x: call(x, None))(x))
    declared = cost_of_jaxpr(jax.make_jaxpr(lambda x: call(
        x, pl.CostEstimate(flops=7, transcendentals=3,
                           bytes_accessed=11)))(x))
    assert (declared.flops, declared.hbm_bytes) == (10.0, 11.0)
    assert walked.flops >= 32 * 128 and walked.hbm_bytes > 11.0

    bh, n, d = 4, 1200, 128                  # 1,200 keys: padded tiles
    q = jax.ShapeDtypeStruct((1, bh, n, d), jnp.bfloat16)
    jx = jax.make_jaxpr(lambda q, k, v: flashomni_dense_attention(
        q, k, v, interpret=True))(q, q, q)
    ce = next(e for e in jx.jaxpr.eqns
              if e.primitive.name == "pallas_call").params["cost_estimate"]
    bq, bk = dense_tiles(n, n)
    n_q, n_k = -(-n // bq) * bq, -(-n // bk) * bk
    assert (ce.flops, ce.transcendentals) == (4 * bh * n * n * d, bh * n * n)
    assert ce.bytes_accessed == 2 * bh * d * (2 * n_q
                                              + 2 * (n_q // bq) * n_k)


def test_memory_hog_is_flagged():
    def hog(x):
        big = jnp.zeros((512, 512), jnp.float32)
        return (x[:, None] * big).sum() + x.sum()

    jx = jax.make_jaxpr(hog)(jax.ShapeDtypeStruct((512,), jnp.float32))
    assert footprint_findings("cost-memory-footprint", "fixture",
                              peak_bytes_of(jx),
                              PEAK_BUDGETS["dispatch_layer"])


# ---------------------------------------------------------------------------
# ISSUE 10: cost passes — green sweep over the real engine
# ---------------------------------------------------------------------------

def test_cost_passes_green_on_real_engine():
    """All four certificates hold on the repo (mesh combos carry a skip
    note on one-device hosts; CI's forced-8-device `make analyze` covers
    them)."""
    ctx = _ctx()
    for cls in COST_PASSES:
        assert cls().run(ctx) == [], f"{cls.name} found regressions"


def test_dispatch_groups_cover_backend_bucket_mesh_grid():
    from repro.analysis.cost_passes import dispatch_groups
    combos = list(dispatch_groups())
    assert len(combos) == 2 * 2 * 2          # backend × kvb × mesh
    live = [(label, cfg) for label, cfg, skip in combos if skip is None]
    assert {c.backend for _, c in live} == {"xla", "pallas"}
    assert {c.kv_buckets for _, c in live} == {1, 3}
    for label, cfg, skip in combos:
        if skip is not None:
            assert cfg is None and "mesh" in label


def test_cli_pass_filter_accepts_globs():
    """`--passes cost-*` selects exactly the four cost passes; a pattern
    matching nothing is an explicit error, not a silent no-op run."""
    from repro.analysis import ALL_PASSES
    import fnmatch
    names = [p.name for p in ALL_PASSES()]
    cost = [n for n in names if fnmatch.fnmatch(n, "cost-*")]
    assert sorted(cost) == ["cost-collective-bytes",
                            "cost-dispatch-scaling",
                            "cost-memory-footprint",
                            "cost-update-amortization"]
    from repro.analysis.__main__ import main
    with pytest.raises(SystemExit, match="match no pass"):
        main(["--passes", "no-such-*", "-q"])


def test_trace_pair_memoizes_per_cfg_and_n():
    from repro.analysis.passes import _TRACE_CACHE, trace_pair
    cfg = _engine_cfg(kv_buckets=1)
    n = 160                               # off-grid: guaranteed cold key
    before = _TRACE_CACHE.misses
    upd1, disp1 = trace_pair(cfg, n=n)
    upd2, disp2 = trace_pair(cfg, n=n)
    assert upd1 is upd2 and disp1 is disp2
    assert _TRACE_CACHE.hits > 0
    # dispatch_only never poisons the full-pair entry
    upd3, _ = trace_pair(cfg, n=n, dispatch_only=False)
    assert upd3 is upd1
    assert _TRACE_CACHE.misses > before   # first call did trace

"""Text-to-vision diffusion pipeline driving the FlashOmni engine.

Rectified-flow Euler sampler: x_{t+dt} = x_t + v_θ(x_t, t)·dt, t: 0 → 1.

The Update–Dispatch schedule (paper §3.2) is TRACED DATA: the engine
config resolves into a :class:`~repro.core.schedule.SparsitySchedule`
(per-step mode array + (step × layer) strategy-id table) and the whole
denoise loop compiles ONCE — a single ``lax.scan`` over steps whose body
``lax.switch``es on the schedule's mode (dense / update / dispatch) and
threads each step's strategy-id row through the scanned DiT blocks.  One
executable per sampling configuration, regardless of step count, schedule
mix, or per-layer deployment tables (enforced by the compile-count test in
``tests/test_schedule.py``).

The pipeline reports the paper's efficiency accounting per step: density
(fraction of live attention work, Fig. 7), sparsity (skip/total, Table 1)
and the Dispatch kernels' live work against their launched grid slots.
Metrics accumulate on device as scan outputs; one host sync after the
loop materializes the whole trace.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.profiler import TraceAnnotation

from repro.configs.base import ArchConfig
from repro.core.engine import (EngineConfig, merge_lane_states,
                               resolve_schedule, schedule_cache_stats)
from repro.core.lru import LruCache
from repro.core.plan import csr_path, live_work
from repro.core.strategy import strategy_key
from repro.core.symbols import unpack_bits
from repro.models import dit

__all__ = ["SamplerConfig", "build_sampler", "sample", "make_lane_tick",
           "make_grouped_lane_tick"]


@dataclasses.dataclass(frozen=True)
class SamplerConfig:
    num_steps: int = 50
    dtype: Any = jnp.float32


def _density_device(states, ecfg: EngineConfig, n_tokens: int) -> jax.Array:
    """Fig. 7 density as a DEVICE scalar (no host sync)."""
    t = ecfg.mask.n_blocks(n_tokens)
    m_c = unpack_bits(states.s_c, t)             # (L, B, H, T)
    return jnp.mean(m_c.astype(jnp.float32))


def _pair_sparsity_device(states, ecfg: EngineConfig, n_tokens: int) -> jax.Array:
    t = ecfg.mask.n_blocks(n_tokens)
    m_c = unpack_bits(states.s_c, t)
    m_s = unpack_bits(states.s_s, t * t).reshape(*states.s_s.shape[:-1], t, t)
    live = m_s & m_c[..., None]
    return 1.0 - jnp.mean(live.astype(jnp.float32))


# Compiled single-scan samplers, keyed on every static of the trace (model /
# engine / sampler configs, shapes, metric mode, schedule strategy
# identities — stable across calls because resolve_schedule memoizes).  A
# second request with the same configuration reuses the first one's
# executable.  LRU-BOUNDED: a long-running server cycling through distinct
# request shapes/schedules evicts the least-recently-served sampler (and
# its pinned strategy tuple) instead of growing without limit; hit/miss
# counters surface through ``stats["sampler_cache"]``.
_SAMPLER_CACHE_SIZE = 32
_SAMPLER_CACHE = LruCache(_SAMPLER_CACHE_SIZE)


def _seq_mesh(ecfg: EngineConfig):
    """``(rules, mesh)`` of a sequence-sharded engine mesh: tokens over
    ``seq``, batch over ``data``; ``None`` elsewhere."""
    if ecfg.mesh_sp <= 1 or ecfg.mesh_axis != "seq":
        return None
    from repro.distributed.sharding import ShardingRules
    from repro.launch.mesh import make_engine_mesh
    return (ShardingRules(dp=("data",), fsdp=(), tp=(), sp=("seq",)),
            make_engine_mesh(ecfg.mesh_dp, ecfg.mesh_sp))


def _token_split(ecfg: EngineConfig):
    """On a sequence-sharded engine mesh, the block's activation hints
    (``distributed.ctx.constrain``) split the tokens over the ``seq`` axis
    and the batch over ``data``, so projections, norms and the MLP run on
    each chip's own tokens; elsewhere they are off."""
    seq = _seq_mesh(ecfg)
    if seq is None:
        return contextlib.nullcontext()
    from repro.distributed.ctx import activation_rules
    return activation_rules(*seq)


def _state_shardings(cfg: ArchConfig, ecfg: EngineConfig):
    """Where a request's engine states live: on a sequence-sharded engine
    mesh as :func:`repro.models.dit.engine_state_specs` lays them out (the
    Taylor state split by token over ``seq``); elsewhere ``None``, the
    default device, as the sampler takes them."""
    seq = _seq_mesh(ecfg)
    if seq is None:
        return None
    from repro.distributed.sharding import named_sharding_tree
    rules, mesh = seq
    return named_sharding_tree(dit.engine_state_specs(cfg, ecfg), mesh,
                               rules)


_INIT_CACHE = LruCache(_SAMPLER_CACHE_SIZE)


def _init_states(cfg: ArchConfig, ecfg: EngineConfig, batch: int,
                 n_tokens: int):
    """A request's fresh engine states, made in place by one compiled call
    (one per shape, kept in an LRU memo) rather than op by op on one chip
    and then moved (:func:`_state_shardings`)."""
    key = (cfg, ecfg, batch, n_tokens)
    fn = _INIT_CACHE.get(key)
    if fn is None:
        fn = _INIT_CACHE.put(key, jax.jit(
            functools.partial(dit.init_engine_states, cfg, ecfg, batch,
                              n_tokens),
            out_shardings=_state_shardings(cfg, ecfg)))
    return fn()


def build_sampler(cfg: ArchConfig, ecfg: EngineConfig, scfg: SamplerConfig,
                  strategies: tuple, batch: int, n_tokens: int,
                  with_metrics: bool):
    """The jitted single-scan sampler that :func:`sample` caches and calls:

        run(params, x0, states, text_emb, patch_embed, mode_arr, id_table)
            -> (x, states, per-step metrics or None)

    The per-step metrics (``with_metrics``) are ``(density, pair_sparsity,
    live, grid)``: ``live`` / ``grid`` map each Dispatch kernel to the
    live work and the launched grid slots of the plan the step leaves in
    the engine state (:func:`repro.core.plan.live_work`, summed over
    layers, for the CSR kernel path :func:`repro.core.plan.csr_path`
    picks at these shapes) — on a Dispatch step, the frozen plan its
    kernels ran on.

    Every op of a step sits under the step mode's named scope (``fo.dense``
    / ``fo.update`` / ``fo.dispatch``) and one block part (``fo.qkv``,
    ``fo.attention``, ``fo.o_proj``, ``fo.symbols``, ``fo.plan``,
    ``fo.cache``, ``fo.mlp``, ``fo.io``), so a profile attributes device
    time by ``op_name``.

    ``strategies`` is the resolved schedule's static strategy set; the
    mode array and strategy-id table are traced operands.  ``states`` is
    donated and returned, so the step loop updates the engine state in
    the argument's buffer: at flux width that saves two copies of the
    57 MB-per-block TaylorSeer state."""
    n_steps = scfg.num_steps
    dt = 1.0 / n_steps

    def step_fn(mode: str):
        def f(params, states, xe, te, t, row, i):
            kw = {}
            if mode == "update":
                kw = dict(strategies=strategies, strategy_row=row,
                          step_idx=i, num_steps=n_steps)
            with jax.named_scope(f"fo.{mode}"), _token_split(ecfg):
                return dit.denoise_step(params, cfg, ecfg, states, xe, te, t,
                                        mode=mode, dtype=scfg.dtype, **kw)
        return f

    branches = [step_fn("dense"), step_fn("update"), step_fn("dispatch")]

    def metrics(states):
        work = live_work(states.plan)
        return (_density_device(states, ecfg, n_tokens),
                _pair_sparsity_device(states, ecfg, n_tokens),
                {k: live for k, (live, _) in work.items()},
                {k: jnp.asarray(slots, jnp.int32)
                 for k, (_, slots) in work.items()})

    def body(params, patch_embed, text_emb, carry, xs):
        x, states = carry
        i, mode, row = xs
        with jax.named_scope("fo.io"):
            t = (jnp.full((batch,), i, jnp.float32) * dt).astype(scfg.dtype)
            xe = (x @ patch_embed).astype(scfg.dtype)
        v, states = jax.lax.switch(mode, branches, params, states, xe,
                                   text_emb, t, row, i)
        with jax.named_scope("fo.io"):
            x = x + v.astype(x.dtype) * dt
        return (x, states), (metrics(states) if with_metrics else None)

    def run(params, x0, states, text_emb, patch_embed, mode_arr, id_table):
        steps = jnp.arange(n_steps, dtype=jnp.int32)
        (x, states), ys = jax.lax.scan(
            lambda c, xs: body(params, patch_embed, text_emb, c, xs),
            (x0, states), (steps, mode_arr, id_table))
        return x, states, ys

    return jax.jit(run, donate_argnums=2)


def sample(params, cfg: ArchConfig, ecfg: EngineConfig, *,
           text_emb: jax.Array, x0: jax.Array, scfg: SamplerConfig = SamplerConfig(),
           patch_embed: Optional[jax.Array] = None,
           trace: Optional[list] = None,
           force_dense: bool = False,
           layer_strategies: Optional[list] = None,
           schedule=None,
           stats: Optional[dict] = None):
    """Run the full sampling loop.  x0: (B, N_v, patch_dim) Gaussian noise.

    The schedule is resolved ONCE on the host
    (:func:`repro.core.engine.resolve_schedule`: ``schedule`` — a named
    preset or prebuilt :class:`~repro.core.schedule.SparsitySchedule` —
    wins over ``layer_strategies`` wins over ``ecfg.schedule`` /
    ``ecfg.strategy``), then the entire denoise loop runs as one jitted
    ``lax.scan`` over ``(step, mode, strategy-id row)``.

    ``patch_embed``: (patch_dim, d_model) stub patchifier.  Returns the
    denoised latents (B, N_v, patch_dim).  ``trace`` (a list) receives one
    ``{step, kind, density, pair_sparsity, live, grid, csr_path}`` dict
    per step (``live`` / ``grid``: live work and launched grid slots per
    Dispatch kernel, see :func:`build_sampler`; ``csr_path``: the CSR
    attention kernel's path, :func:`repro.core.plan.csr_path`); ``stats`` (a
    dict) receives ``executables`` (compiled-executable count for this
    call — exactly 1), ``lower`` (a thunk that lowers the sampler at this
    call's arguments, to inspect the compiled program), ``schedule`` (the
    resolved schedule) and the ``sampler_cache`` / ``schedule_cache``
    hit/miss/eviction counters of the two LRU-bounded serving memos.

    Host spans (``jax.profiler.TraceAnnotation``, free while no profiler
    runs) name the call's host work on the profile's clock: ``fo.states``,
    ``fo.schedule``, ``fo.launch`` (argument ``compiled``: the call
    compiled) and ``fo.metrics``.
    """
    b, nv, pd = x0.shape
    n_tokens = nv + text_emb.shape[1]
    n_steps = scfg.num_steps
    with TraceAnnotation("fo.states"):
        states = _init_states(cfg, ecfg, b, n_tokens)
    if patch_embed is None:
        patch_embed = jax.random.normal(jax.random.PRNGKey(7), (pd, cfg.d_model)) * 0.2

    with TraceAnnotation("fo.schedule"):
        sched = resolve_schedule(ecfg, n_steps, cfg.n_layers,
                                 schedule=schedule,
                                 layer_strategies=layer_strategies,
                                 force_dense=force_dense)
    with_metrics = trace is not None

    key = (cfg, ecfg, scfg, n_steps, with_metrics, b, nv, pd,
           text_emb.shape[1], x0.dtype, text_emb.dtype, patch_embed.dtype,
           tuple(strategy_key(s) for s in sched.strategies))
    entry = _SAMPLER_CACHE.get(key)
    missed = entry is None
    if missed:
        # Registry strategies key by VALUE (strategy_key), so a schedule
        # re-resolved after an LRU eviction of the resolve_schedule memo
        # still HITS this cache; ad-hoc strategies key by id() and pin
        # their strategies tuple alive next to the compiled fn so the id
        # can never alias a recycled object.
        fn = build_sampler(cfg, ecfg, scfg, sched.strategies, b, n_tokens,
                           with_metrics)
        entry = _SAMPLER_CACHE.put(key, (fn, sched.strategies))
    fn = entry[0]
    args = (params, x0, states, text_emb, patch_embed, sched.mode,
            sched.strategy_ids)
    cache_size = getattr(fn, "_cache_size", None)
    size = cache_size() if cache_size else 0
    with TraceAnnotation("fo.launch") as span:
        x, _, ys = fn(*args)
        # Names a compile in the profile: a new sampler, or a new
        # executable of a cached one (another argument signature).
        span.set_metadata(compiled=missed or bool(
            cache_size and cache_size() > size))
    if stats is not None:
        stats["executables"] = int(cache_size()) if cache_size else -1
        # Abstract arguments: the thunk pins no device buffer, and the
        # donated ``states`` are gone by now.  An uncommitted argument
        # (made on the default device) keeps no sharding, as the call
        # left it free to move beside weights placed on a mesh.
        stats["lower"] = functools.partial(fn.lower, *jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(
                a.shape, a.dtype,
                sharding=a.sharding if getattr(a, "committed", False)
                else None),
            args))
        stats["schedule"] = sched
        stats["sampler_cache"] = _SAMPLER_CACHE.stats()
        stats["schedule_cache"] = schedule_cache_stats()
    if with_metrics:
        kinds = sched.kinds()
        path = csr_path(ecfg, n_tokens, cfg.hd, scfg.dtype)
        with TraceAnnotation("fo.metrics"):
            # ONE host sync for the trace
            dens, pair_s, live, grid = jax.device_get(ys)
            for i in range(n_steps):
                trace.append({
                    "step": i, "kind": kinds[i], "density": float(dens[i]),
                    "pair_sparsity": float(pair_s[i]),
                    "live": {k: int(v[i]) for k, v in live.items()},
                    "grid": {k: int(v[i]) for k, v in grid.items()},
                    "csr_path": path})
    return x


def make_lane_tick(cfg: ArchConfig, ecfg: EngineConfig,
                   scfg: SamplerConfig, strategies: tuple,
                   with_metrics: bool = True):
    """Build the continuous batcher's lane-serial serving tick (fallback).

    One tick advances every lane of a fixed-width microbatch by ONE
    denoising step.  The tick body is a ``lax.scan`` over the LANE axis
    whose body selects each lane's ``(mode, strategy-id row)`` from the
    lane's OWN traced schedule table at the lane's own step counter
    (``SparsitySchedule``s of different lengths pad with ``MODE_IDLE`` —
    see :func:`repro.core.schedule.stack_schedules`), then ``lax.switch``es
    into the same dense/update/dispatch trace bodies as :func:`sample` —
    per-lane numerics are bit-identical to a sequential run of the same
    request (the acceptance criterion of the serving benchmark), because
    each lane body executes exactly the single-request op sequence at the
    single-request shapes.  Mode-HOMOGENEOUS ticks should instead run a
    batched mode body from :func:`make_grouped_lane_tick` (lane
    parallelism on the batch axis); this scan handles the genuinely mixed
    remainders, where the per-lane ``lax.switch`` is unavoidable.

    The returned function is jitted ONCE per lane shape — lanes retire
    and refill by swapping traced data (tables, step counters, state
    slices), never by re-tracing:

        tick(params, patch_embed, x, states, text_emb, step, mode_tab,
             id_tab, dt, nsteps, active, reset) -> (x', states', density,
                                                    pair_sparsity)

    with ``x`` (lanes, B, N_v, patch_dim); ``states`` lane-stacked engine
    states (:func:`repro.core.engine.stack_lane_states`); ``text_emb``
    (lanes, B, N_t, d_model); ``step`` (lanes,) int32 per-lane step
    counters; ``mode_tab`` (lanes, S) / ``id_tab`` (lanes, S, L) the
    stacked schedule tables; ``dt`` (lanes,) f32 per-lane 1/num_steps;
    ``nsteps`` (lanes,) int32 per-lane TOTAL step counts — threaded into
    ``StrategyContext.num_steps`` as a traced scalar so schedule-varying
    producers (``step-phased`` fractional boundaries) behave exactly as
    under ``pipeline.sample``; ``active`` (lanes,) bool; ``reset``
    (lanes,) bool — True for lanes REFILLED since the last tick, whose
    engine state is re-initialized ON DEVICE before stepping (the fresh
    state is a trace constant, so refill costs zero host-side state
    dispatches — only the lane's latent/text buffers are host-written).
    Idle lanes (``active`` false or table padding) run a no-op branch:
    latents/state pass through and their metric outputs are EXACTLY zero.

    ``with_metrics=False`` skips the per-lane density/pair-sparsity
    reductions (the outputs are zeros) — the pure-throughput serving
    configuration; it is a trace-time static, part of the tick key.
    """
    from repro.core.schedule import MODE_IDLE

    def tick(params, patch_embed, x, states, text_emb, step, mode_tab,
             id_tab, dt, nsteps, active, reset):
        b = x.shape[1]
        n_tokens = x.shape[2] + text_emb.shape[2]
        fresh = dit.init_engine_states(cfg, ecfg, b, n_tokens)

        def branch(mode: str):
            def f(x, st, xe, te, t, row, i, dts, ns):
                kw = {}
                if mode == "update":
                    kw = dict(strategies=strategies, strategy_row=row,
                              step_idx=i, num_steps=ns)
                v, st2 = dit.denoise_step(params, cfg, ecfg, st, xe, te, t,
                                          mode=mode, dtype=scfg.dtype, **kw)
                # dts is a STRONG f32 scalar (sample()'s dt is a weak
                # Python float): cast to x.dtype so non-f32 latents are
                # not promoted — the tick's output dtype must equal its
                # input dtype or the next tick recompiles.
                x2 = x + v.astype(x.dtype) * dts.astype(x.dtype)
                if not with_metrics:
                    return (x2, st2, jnp.zeros((), jnp.float32),
                            jnp.zeros((), jnp.float32))
                return (x2, st2, _density_device(st2, ecfg, n_tokens),
                        _pair_sparsity_device(st2, ecfg, n_tokens))
            return f

        def idle(x, st, xe, te, t, row, i, dts, ns):
            return (x, st, jnp.zeros((), jnp.float32),
                    jnp.zeros((), jnp.float32))

        branches = [branch("dense"), branch("update"), branch("dispatch"),
                    idle]

        def lane(_, xs):
            x, st, te, i, mrow, irow, dts, ns, act, rst = xs
            # Freshly refilled lane: re-initialize its engine state from
            # the trace-constant init tree before stepping.
            st = jax.tree.map(
                lambda s, f: jnp.where(rst, f.astype(s.dtype), s), st, fresh)
            ic = jnp.clip(i, 0, mrow.shape[0] - 1)
            mode = jnp.where(act, mrow[ic], MODE_IDLE)
            t = (jnp.full((b,), i, jnp.float32) * dts).astype(scfg.dtype)
            xe = (x @ patch_embed).astype(scfg.dtype)
            out = jax.lax.switch(mode, branches, x, st, xe, te, t, irow[ic],
                                 i, dts, ns)
            return None, out

        _, (x2, st2, dens, ps) = jax.lax.scan(
            lane, None,
            (x, states, text_emb, step, mode_tab, id_tab, dt, nsteps,
             active, reset))
        return x2, st2, dens, ps

    return jax.jit(tick)


def make_grouped_lane_tick(cfg: ArchConfig, ecfg: EngineConfig,
                           scfg: SamplerConfig, strategies: tuple,
                           with_metrics: bool = True):
    """Build the batched MODE-GROUP serving ticks (same-mode lane folding).

    The continuous batcher's lane tables are host-visible, so before
    launching a tick the host knows every lane's ``(mode, strategy-id
    row)`` (:func:`repro.core.schedule.tick_mode_groups`).  When every
    active lane is in the SAME mode, the lane scan's per-lane
    ``lax.switch`` is pure overhead — the tick is one batched
    dense/update/dispatch step over the lanes folded into the model's
    batch axis.  This factory returns ``{"dense", "update", "dispatch"}``
    → jitted group bodies, each:

        body(params, patch_embed, x, states, text_emb, step, id_rows, dt,
             nsteps, lane_mask, reset) -> (x', states', density,
                                           pair_sparsity)

    Arguments match :func:`make_lane_tick` except the schedule tables are
    replaced by the CURRENT-step slice: ``id_rows`` (lanes, L) int32 — the
    per-lane strategy-id rows at each lane's own step (update body only;
    dense/dispatch ignore them) — and ``lane_mask`` (lanes,) bool selects
    the group.  The body ``jax.vmap``s the single-lane step over the lane
    axis — every per-sample op is the batch-axis fold of the sequential
    op sequence (the stacked-serving bit-parity guarantee), and per-lane
    traced context (step counter, ``dt``, ``num_steps``, TaylorSeer
    ``k_since`` offsets, strategy-id rows) batches with it; per-lane
    outputs stay BIT-identical to sequential runs.  Lanes outside
    ``lane_mask`` are computed (the executable's shape is lane-count
    fixed, never group-sized) and then discarded by a masked lane merge
    (:func:`repro.core.engine.merge_lane_states`): latents/state pass
    through and metrics are EXACTLY zero, the same contract as the scan
    tick's idle branch.

    Each body is jitted ONCE per lane shape; with the scan fallback that
    is a fixed, shape-independent executable budget of ≤ 4 per lane shape
    (dense / update / dispatch / mixed-fallback), regardless of schedule
    variety, group sizes, or how lanes retire and refill.  Strategy-id
    rows are TRACED, so two update groups with different rows are two
    CALLS of one executable; a heterogeneous row mix inside one update
    group is legal too (``emit_switch``'s ``lax.switch`` batches into an
    all-branch select under ``vmap`` — bit-exact, at the cost of running
    every emitter) — the batcher only folds same-mode lanes, which keeps
    the common homogeneous tick on the cheap path.
    """

    def make(mode: str):
        def body(params, patch_embed, x, states, text_emb, step, id_rows,
                 dt, nsteps, lane_mask, reset):
            b = x.shape[1]
            n_tokens = x.shape[2] + text_emb.shape[2]
            lanes = x.shape[0]
            fresh = jax.tree.map(
                lambda f: jnp.broadcast_to(f, (lanes, *f.shape)),
                dit.init_engine_states(cfg, ecfg, b, n_tokens))
            states = merge_lane_states(states, fresh, reset)

            def lane(x_l, st_l, te_l, i, row, dts, ns):
                t = (jnp.full((b,), i, jnp.float32) * dts).astype(scfg.dtype)
                xe = (x_l @ patch_embed).astype(scfg.dtype)
                kw = {}
                if mode == "update":
                    kw = dict(strategies=strategies, strategy_row=row,
                              step_idx=i, num_steps=ns)
                v, st2 = dit.denoise_step(params, cfg, ecfg, st_l, xe, te_l,
                                          t, mode=mode, dtype=scfg.dtype,
                                          **kw)
                x2 = x_l + v.astype(x_l.dtype) * dts.astype(x_l.dtype)
                if not with_metrics:
                    return (x2, st2, jnp.zeros((), jnp.float32),
                            jnp.zeros((), jnp.float32))
                return (x2, st2, _density_device(st2, ecfg, n_tokens),
                        _pair_sparsity_device(st2, ecfg, n_tokens))

            x2, st2, dens, ps = jax.vmap(lane)(x, states, text_emb, step,
                                               id_rows, dt, nsteps)
            x_out = merge_lane_states(x, x2, lane_mask)
            st_out = merge_lane_states(states, st2, lane_mask)
            zero = jnp.zeros((), jnp.float32)
            return (x_out, st_out, jnp.where(lane_mask, dens, zero),
                    jnp.where(lane_mask, ps, zero))

        return jax.jit(body)

    return {"dense": make("dense"), "update": make("update"),
            "dispatch": make("dispatch")}

"""Continuous-batching request queue over mixed SparsitySchedules.

The paper's deployment scenario is a served Hunyuan-class model under
real traffic.  Single-request serving leaves two wins on the table:

  * **Stacked batching** — requests that share a data shape AND a
    resolved schedule are pure batch parallelism: concatenate them on the
    batch axis and run the cached single-scan sampler once.  Per-lane
    outputs are BIT-IDENTICAL to sequential runs (batch stacking changes
    no per-sample op shapes' reduction axes), test-enforced.
  * **Continuous batching** — requests whose schedules differ (length,
    strategy mix, per-layer tables) cannot stack, but they CAN interleave:
    a fixed-width microbatch of lanes, each holding one request, advances
    every lane by one denoising step per serving tick.  The host reads
    each lane's ``(mode, strategy-id row)`` from the lane's own schedule
    table BEFORE launching the tick: a mode-homogeneous tick folds the
    lanes into the model's batch axis through one batched mode body
    (same-mode lane folding — stacked-level lane parallelism), and only
    genuinely mixed ticks take the lane-serial scan whose body
    ``lax.switch``es per lane.  Either way the tables are TRACED
    (:func:`repro.core.schedule.stack_schedules` pads mixed lengths with
    ``MODE_IDLE``), so lanes retire and refill WITHOUT recompiling — a
    fixed budget of at most FOUR executables per distinct lane shape
    (dense/update/dispatch group bodies + the mixed fallback), regardless
    of how many schedule variants flow through (the xDiT / Sparse-vDiT
    serving observation: keep heterogeneous sparse configs resident in
    one engine).  A sequential server instead pays one compiled sampler
    per distinct configuration.

Module contents:

  * :class:`Request` / :class:`RequestQueue` — arrival-ordered FIFO.
  * :func:`run_sequential`    — baseline: one ``pipeline.sample`` per
    request (shares compiled samplers via the pipeline's LRU cache).
  * :func:`run_stacked`       — group by (shape, schedule), stack on the
    batch axis, one sampler call per group.
  * :class:`ContinuousBatcher` — the lane engine described above.

``benchmarks/bench_serving.py`` measures all three (req/s, p50/p95
latency) and asserts the per-lane bit-parity acceptance criterion.
"""

from __future__ import annotations

import bisect
import dataclasses
import time
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.configs.base import ArchConfig
from repro.core.engine import (EngineConfig, resolve_schedule,
                               stack_lane_states)
from repro.core.schedule import (MODE_IDLE, MODE_NAMES, merge_strategies,
                                 schedule_lane_rows, tick_mode_groups)
from repro.core.strategy import strategy_key
from repro.diffusion.pipeline import (SamplerConfig, make_grouped_lane_tick,
                                      make_lane_tick, sample)
from repro.models import dit

__all__ = ["Request", "RequestQueue", "ContinuousBatcher",
           "run_sequential", "run_stacked", "default_patch_embed"]


def default_patch_embed(cfg: ArchConfig, patch_dim: int) -> jax.Array:
    """The stub patchifier ``pipeline.sample`` defaults to — every serving
    mode must share it or per-lane parity is meaningless."""
    return jax.random.normal(jax.random.PRNGKey(7),
                             (patch_dim, cfg.d_model)) * 0.2


@dataclasses.dataclass
class Request:
    """One text-to-vision serving request.

    ``x0`` (B, N_v, patch_dim) Gaussian latents; ``text_emb`` (B, N_t,
    d_model); ``schedule`` / ``layer_strategies`` feed
    :func:`repro.core.engine.resolve_schedule` against the server's shared
    ``EngineConfig`` (``None`` → the config's own strategy/interval
    mapping).  ``arrival`` is seconds since the serving clock's start.
    """

    rid: Any
    x0: jax.Array
    text_emb: jax.Array
    num_steps: int
    schedule: Any = None
    layer_strategies: Any = None
    arrival: float = 0.0

    def resolve(self, ecfg: EngineConfig, n_layers: int):
        return resolve_schedule(ecfg, self.num_steps, n_layers,
                                schedule=self.schedule,
                                layer_strategies=self.layer_strategies)

    def shape_key(self) -> tuple:
        """Lane-shape key: requests in one microbatch must agree on it."""
        return (self.x0.shape, str(self.x0.dtype),
                self.text_emb.shape, str(self.text_emb.dtype))


class RequestQueue:
    """Arrival-ordered FIFO (stable for equal arrival times)."""

    def __init__(self):
        self._items: list[tuple[float, int, Request]] = []
        self._seq = 0

    def submit(self, req: Request) -> None:
        # The backing list is kept sorted by (arrival, seq) at all times,
        # so one bisect insertion is O(log n) compares + O(n) moves —
        # re-sorting the whole list per insert made submit_all O(n² log n).
        # The monotone ``seq`` tiebreak means the comparison never reaches
        # the (unorderable) Request itself and equal arrivals stay FIFO.
        bisect.insort(self._items, (req.arrival, self._seq, req))
        self._seq += 1

    def submit_all(self, reqs) -> None:
        for r in reqs:
            self.submit(r)

    def __len__(self) -> int:
        return len(self._items)

    def pending(self) -> list[Request]:
        return [r for _, _, r in self._items]

    def next_arrival(self) -> Optional[float]:
        return self._items[0][0] if self._items else None

    def pop_ready(self, now: float) -> Optional[Request]:
        """Pop the earliest request whose arrival time has passed."""
        if self._items and self._items[0][0] <= now:
            return self._items.pop(0)[2]
        return None


# ---------------------------------------------------------------------------
# Sequential + stacked serving (the baselines the batcher must beat)
# ---------------------------------------------------------------------------

def _result(out, trace, arrival, finish):
    return {"out": out, "trace": trace, "finish": finish,
            "latency": finish - arrival}


def run_sequential(params, cfg: ArchConfig, ecfg: EngineConfig, requests,
                   *, scfg_dtype=jnp.float32, patch_embed=None,
                   collect_traces: bool = True,
                   stats: Optional[dict] = None) -> dict:
    """Baseline server: requests strictly one after another (arrival
    order), each through its own ``pipeline.sample`` call.  Compiled
    samplers are shared across same-config requests via the pipeline's
    LRU cache; every DISTINCT configuration still pays its own compile.
    ``stats`` receives ``pipeline.sample``'s stats of each request in
    turn (the last request's remain).  Each request runs inside a host
    span ``fo.request`` (argument ``rid``), with ``fo.wait`` around the
    wait for the device and ``fo.fetch`` around the copy to the host."""
    if patch_embed is None and requests:
        patch_embed = default_patch_embed(cfg, requests[0].x0.shape[-1])
    results: dict = {}
    t0 = time.perf_counter()
    for req in sorted(requests, key=lambda r: r.arrival):
        now = time.perf_counter() - t0
        if now < req.arrival:
            time.sleep(req.arrival - now)
        trace: list = [] if collect_traces else None
        with TraceAnnotation("fo.request", rid=req.rid):
            out = sample(params, cfg, ecfg, text_emb=req.text_emb, x0=req.x0,
                         scfg=SamplerConfig(num_steps=req.num_steps,
                                            dtype=scfg_dtype),
                         patch_embed=patch_embed, trace=trace,
                         schedule=req.schedule,
                         layer_strategies=req.layer_strategies, stats=stats)
            with TraceAnnotation("fo.wait"):
                jax.block_until_ready(out)
            with TraceAnnotation("fo.fetch"):
                out = np.asarray(out)
        results[req.rid] = _result(out, trace, req.arrival,
                                   time.perf_counter() - t0)
    return results


def run_stacked(params, cfg: ArchConfig, ecfg: EngineConfig, requests,
                *, scfg_dtype=jnp.float32, patch_embed=None) -> dict:
    """Stack same-shape/same-schedule requests into one batch axis.

    Grouping key = (data shapes, resolved-schedule identity): thanks to
    the memoized :func:`resolve_schedule`, equal specs resolve to the SAME
    schedule object, so grouping by ``id(schedule)`` is exact — each group
    VALUE pins its schedule object alive, so an id can never be recycled
    by a different schedule while grouping (the resolution memo is
    LRU-bounded and may drop its own reference).  Each group runs ONE
    cached single-scan sampler call over the concatenated batch; outputs
    split back per request and are bit-identical to sequential runs
    (test-enforced).  A group starts once ALL its members arrived.
    Per-request traces are not recorded — step metrics of a stacked run
    average over the whole stacked batch (use the continuous batcher for
    per-lane metrics).
    """
    if patch_embed is None and requests:
        patch_embed = default_patch_embed(cfg, requests[0].x0.shape[-1])
    groups: dict[tuple, tuple] = {}
    for req in sorted(requests, key=lambda r: r.arrival):
        sched = req.resolve(ecfg, cfg.n_layers)
        groups.setdefault((req.shape_key(), req.num_steps, id(sched)),
                          (sched, []))[1].append(req)
    results: dict = {}
    t0 = time.perf_counter()
    for (_, num_steps, _), (_, members) in groups.items():
        ready = max(r.arrival for r in members)
        now = time.perf_counter() - t0
        if now < ready:
            time.sleep(ready - now)
        x0 = jnp.concatenate([r.x0 for r in members], axis=0)
        text = jnp.concatenate([r.text_emb for r in members], axis=0)
        out = sample(params, cfg, ecfg, text_emb=text, x0=x0,
                     scfg=SamplerConfig(num_steps=num_steps,
                                        dtype=scfg_dtype),
                     patch_embed=patch_embed,
                     schedule=members[0].schedule,
                     layer_strategies=members[0].layer_strategies)
        jax.block_until_ready(out)
        finish = time.perf_counter() - t0
        off = 0
        for r in members:
            b = r.x0.shape[0]
            results[r.rid] = _result(np.asarray(out[off:off + b]), None,
                                     r.arrival, finish)
            off += b
    return results


# ---------------------------------------------------------------------------
# Continuous batcher
# ---------------------------------------------------------------------------

def _lockstep_capable(schedules) -> bool:
    """True when every queued schedule shares one mode table and length.

    The ``grouped="auto"`` policy input: such a mix keeps resident lanes
    mode-homogeneous whenever they fill together, so the batched
    mode-group bodies earn their compiles; any other mix de-synchronizes
    and would mostly pay for executables the scan fallback replaces."""
    ref: Optional[np.ndarray] = None
    for sched in schedules:
        mode = np.asarray(sched.mode)
        if ref is None:
            ref = mode
        elif mode.shape != ref.shape or not np.array_equal(mode, ref):
            return False
    return True

class ContinuousBatcher:
    """Fixed-width microbatch server over mixed SparsitySchedules.

    ``lanes`` requests are resident at once; every serving tick advances
    each active lane by one denoising step.  A lane whose request reaches
    its own ``num_steps`` RETIRES (output captured) and REFILLS from the
    queue as soon as a request's arrival time passes — all by swapping
    traced data, so the ticks never recompile:

      * per-lane ``(mode, strategy-id)`` rows come from the stacked
        schedule tables (``MODE_IDLE``-padded, strategy ids remapped onto
        the merged strategy universe of all queued requests);
      * per-lane engine states re-initialize ON DEVICE via the tick's
        traced ``reset`` mask (the fresh state is a trace constant), so a
        refill host-writes only the lane's latent/text buffers;
      * empty lanes pass through and contribute EXACTLY zero to the
        per-lane metric outputs (test-enforced).

    Tick dispatch (same-mode lane folding): the lane tables are
    host-visible, so each tick partitions the active lanes by current
    mode (:func:`repro.core.schedule.tick_mode_groups`).  A mode-
    HOMOGENEOUS tick — the steady state whenever resident lanes run the
    same schedule phase, e.g. a homogeneous request mix in lockstep —
    runs one batched mode body (:func:`repro.diffusion.pipeline.
    make_grouped_lane_tick`): the lanes fold into the model's batch axis
    and advance in parallel, recovering stacked-serving throughput.
    Genuinely mixed ticks fall back to the lane-serial scan tick.  The
    compiled-executable budget is FIXED and shape-independent: at most 4
    per distinct lane shape (dense / update / dispatch group bodies + the
    mixed fallback; ``stats["executables"]``, test-enforced ≤ 4), and
    per-lane outputs are bit-identical to sequential runs of the same
    requests on either path (the serving benchmark asserts this).

    ``max_steps`` fixes the padded schedule-table width (default: longest
    queued schedule at ``run`` time; a fixed value keeps the lane shape —
    and hence the executables — stable across ``run`` calls).

    ``shape_buckets`` (ISSUE 6 tentpole, the lane-level analogue of the
    kernel's occupancy buckets): a production mix of NEAR-MISS resolutions
    fragments the exact-``shape_key()`` partitioning into many lane
    partitions, each paying its own compile.  Passing a small tuple of
    canonical vision-token counts (e.g. ``(64, 96, 128)``) rounds each
    request's ``N_v`` UP to the smallest bucket that fits at admission —
    the latent is zero-padded into the lane buffer and the output sliced
    back to the request's own length — so near-miss shapes share ONE lane
    executable and the ≤ 4-executable budget holds across the mix.  A
    request larger than every bucket passes through at its own shape.
    Per-request outputs equal a sequential run of the same PADDED request
    sliced identically (bit-parity test-enforced); the mapping actually
    used is reported in ``stats["shape_buckets"]`` (the lane-bucket map
    ``serve.py --serving continuous`` prints).

    ``grouped`` picks the folding policy.  ``"auto"`` (default) enables
    the mode-group bodies for a ``run`` only when every queued request
    resolves to the SAME mode table and length — the lockstep-capable mix
    where folding recovers stacked-level throughput; a heterogeneous mix
    would compile group bodies it can rarely use (every de-synchronized
    tick takes the scan anyway), so auto keeps it on the one-executable
    scan and preserves the cold-serving win over sequential.  ``True``
    folds every mode-homogeneous tick regardless of the queued mix;
    ``False`` disables folding entirely (the safety valve for backends
    whose kernels cannot lower under ``vmap``).  ``with_metrics=False``
    skips the per-tick density / pair-sparsity reductions for
    pure-throughput serving (lane metric stats and per-request trace
    metrics read as zero).
    """

    def __init__(self, params, cfg: ArchConfig, ecfg: EngineConfig, *,
                 lanes: int = 4, max_steps: Optional[int] = None,
                 scfg_dtype=jnp.float32, patch_embed=None,
                 sync_every_tick: bool = True, grouped="auto",
                 with_metrics: bool = True,
                 shape_buckets: Optional[tuple] = None):
        self.params = params
        self.cfg = cfg
        self.ecfg = ecfg
        self.lanes = int(lanes)
        self.max_steps = max_steps
        self.shape_buckets = (tuple(sorted(int(s) for s in shape_buckets))
                              if shape_buckets else ())
        self.scfg = SamplerConfig(num_steps=0, dtype=scfg_dtype)
        self.patch_embed = patch_embed
        self.sync_every_tick = sync_every_tick
        self.grouped = grouped
        self.with_metrics = with_metrics
        if grouped not in ("auto", True, False):
            raise ValueError(f"grouped must be 'auto', True or False, "
                             f"got {grouped!r}")
        self.queue = RequestQueue()
        self.stats: dict = {}
        self._tick = None
        self._grouped_ticks: Optional[dict] = None
        self._use_grouped = False        # per-run policy decision
        self._universe: tuple = ()
        self._retired_executables = 0    # compiled by discarded tick jits

    def submit(self, req: Request) -> None:
        self.queue.submit(req)

    def submit_all(self, reqs) -> None:
        self.queue.submit_all(reqs)

    # -- internals --------------------------------------------------------

    def _bucket_nv(self, nv: int) -> int:
        """Smallest canonical vision length that fits ``nv`` (or ``nv``)."""
        for b in self.shape_buckets:
            if b >= nv:
                return b
        return nv

    def _canon_key(self, req: Request) -> tuple:
        """``shape_key()`` with ``N_v`` rounded up to its shape bucket."""
        b, nv, pd = req.x0.shape
        return ((b, self._bucket_nv(nv), pd), str(req.x0.dtype),
                req.text_emb.shape, str(req.text_emb.dtype))

    def _cache_sizes(self) -> int:
        """Live compiled-executable count across all tick jits."""
        fns = [self._tick] + (list(self._grouped_ticks.values())
                              if self._grouped_ticks else [])
        return sum(int(f._cache_size()) for f in fns if f is not None)

    def _ensure_tick(self, schedules) -> None:
        """(Re)build the jitted ticks when the strategy universe grows.

        The universe is the ticks' STATIC closure; growing it re-traces.
        Requests whose strategies are already resident — by VALUE
        (:func:`repro.core.strategy.strategy_key`), so a re-resolved spec
        whose memo entry was LRU-evicted still counts as resident — never
        do."""
        known = {strategy_key(s) for s in self._universe}
        new: list = []
        for sched in schedules:
            for s in sched.strategies:
                key = strategy_key(s)
                if key not in known:
                    known.add(key)
                    new.append(s)
        if self._tick is None or new:
            if self._tick is not None:
                # A growing universe re-traces EVERYTHING — keep the old
                # ticks' executables in the count so the recompile is
                # visible in stats["executables"].
                self._retired_executables += self._cache_sizes()
            self._universe = self._universe + tuple(new)
            self._tick = make_lane_tick(self.cfg, self.ecfg, self.scfg,
                                        self._universe, self.with_metrics)
            self._grouped_ticks = (
                make_grouped_lane_tick(self.cfg, self.ecfg, self.scfg,
                                       self._universe, self.with_metrics)
                if self.grouped else None)

    def run(self) -> dict:
        """Drain the queue; returns {rid: {out, trace, latency, finish}}.

        Requests are partitioned by lane shape (each partition runs the
        microbatch loop with its own lane buffers; partitions share the
        jitted tick, so ``stats["executables"]`` counts one executable
        per distinct lane shape)."""
        reqs = [self.queue.pop_ready(float("inf"))
                for _ in range(len(self.queue))]
        scheds = {id(r): r.resolve(self.ecfg, self.cfg.n_layers)
                  for r in reqs}
        self._ensure_tick(scheds.values())
        self._use_grouped = self._grouped_ticks is not None and (
            self.grouped is True or _lockstep_capable(scheds.values()))
        s_max = self.max_steps or max((r.num_steps for r in reqs), default=1)
        # Shape-bucketed partitioning: near-miss N_v resolutions fold into
        # one canonical lane shape (see class docstring) instead of each
        # compiling its own partition.
        by_shape: dict[tuple, list[Request]] = {}
        bucket_map: dict[tuple, tuple] = {}
        for r in reqs:
            key = self._canon_key(r)
            bucket_map[r.shape_key()] = key
            by_shape.setdefault(key, []).append(r)
        results: dict = {}
        total_ticks = 0
        grouped_ticks = 0
        lane_density: list[np.ndarray] = []
        lane_pairs: list[np.ndarray] = []
        lane_active: list[np.ndarray] = []
        # ONE serving clock across partitions: latency/finish times and
        # arrival simulation include time spent queued behind an earlier
        # lane-shape partition.
        t0 = time.perf_counter()
        for key, shape_reqs in by_shape.items():
            q = RequestQueue()
            q.submit_all(shape_reqs)
            part, ticks, gticks, dens, ps, act = self._run_partition(
                q, scheds, s_max, t0, nv_lane=key[0][1])
            results.update(part)
            total_ticks += ticks
            grouped_ticks += gticks
            lane_density.append(dens)
            lane_pairs.append(ps)
            lane_active.append(act)
        self.stats = {
            "executables": self._cache_sizes() + self._retired_executables,
            "ticks": total_ticks,
            "grouped_ticks": grouped_ticks,
            "scan_ticks": total_ticks - grouped_ticks,
            "lanes": self.lanes,
            "max_steps": s_max,
            "strategies": [s.name for s in self._universe],
            "lane_density": (np.concatenate(lane_density)
                             if lane_density else np.zeros((0, self.lanes))),
            "lane_pair_sparsity": (np.concatenate(lane_pairs)
                                   if lane_pairs else
                                   np.zeros((0, self.lanes))),
            "lane_active": (np.concatenate(lane_active)
                            if lane_active else
                            np.zeros((0, self.lanes), bool)),
            "shape_buckets": bucket_map,
            "shape_partitions": len(by_shape),
        }
        return results

    def _run_partition(self, q: RequestQueue, scheds: dict, s_max: int,
                       t0: float, nv_lane: Optional[int] = None):
        cfg, ecfg, W = self.cfg, self.ecfg, self.lanes
        probe = q.pending()[0]
        b, nv, pd = probe.x0.shape
        # The partition's canonical (bucketed) vision length; requests
        # shorter than the lane are zero-padded in and sliced back out.
        nv = nv if nv_lane is None else nv_lane
        nt, dm = probe.text_emb.shape[1], cfg.d_model
        n_tokens = nv + nt
        patch_embed = self.patch_embed
        if patch_embed is None:
            patch_embed = default_patch_embed(cfg, pd)

        x = jnp.zeros((W, b, nv, pd), probe.x0.dtype)
        text = jnp.zeros((W, b, nt, dm), probe.text_emb.dtype)
        states = stack_lane_states(
            dit.init_engine_states(cfg, ecfg, b, n_tokens), W)
        mode_tab = np.full((W, s_max), MODE_IDLE, np.int32)
        id_tab = np.zeros((W, s_max, cfg.n_layers), np.int32)
        dt = np.zeros((W,), np.float32)
        nsteps = np.zeros((W,), np.int32)
        steps = np.zeros((W,), np.int32)
        active = np.zeros((W,), bool)
        reset = np.zeros((W,), bool)
        lane_req: list[Optional[Request]] = [None] * W

        results: dict = {}
        pending_out: list = []
        tick_log: list = []
        hist: list = []
        act_log: list = []
        ticks = 0
        grouped_ticks = 0
        while len(q) or active.any():
            now = time.perf_counter() - t0
            for w in range(W):
                if active[w]:
                    continue
                req = q.pop_ready(now)
                if req is None:
                    break
                sched = scheds[id(req)]
                mrow, irow = schedule_lane_rows(sched, self._universe, s_max)
                mode_tab[w], id_tab[w] = mrow, irow
                dt[w] = np.float32(1.0 / req.num_steps)
                nsteps[w] = req.num_steps
                x0w = req.x0
                if x0w.shape[1] < nv:      # shape-bucket zero pad
                    x0w = jnp.pad(
                        x0w, ((0, 0), (0, nv - x0w.shape[1]), (0, 0)))
                x = x.at[w].set(x0w)
                text = text.at[w].set(req.text_emb)
                # Engine state re-initializes ON DEVICE inside the tick
                # (traced `reset` mask -> trace-constant fresh state): a
                # refill costs two latent/text writes, not a whole
                # LayerState pytree of host dispatches.
                reset[w] = True
                steps[w], active[w], lane_req[w] = 0, True, req
            if not active.any():
                # Nothing resident and nothing ready yet: idle until the
                # next arrival instead of burning no-op ticks.
                na = q.next_arrival()
                wait = 0.0 if na is None else na - (time.perf_counter() - t0)
                if wait > 0:
                    time.sleep(wait)
                continue
            groups = tick_mode_groups(mode_tab, steps, active)
            if self._use_grouped and len(groups) == 1:
                # Mode-homogeneous tick: fold the lanes into the model
                # batch axis through the matching mode-group body.
                mode, mask = groups[0]
                id_rows = id_tab[np.arange(W), np.clip(steps, 0, s_max - 1)]
                x, states, dens, ps = self._grouped_ticks[MODE_NAMES[mode]](
                    self.params, patch_embed, x, states, text,
                    jnp.asarray(steps), jnp.asarray(id_rows),
                    jnp.asarray(dt), jnp.asarray(nsteps), jnp.asarray(mask),
                    jnp.asarray(reset))
                grouped_ticks += 1
            else:
                # Genuinely mixed modes: lane-serial scan fallback.
                x, states, dens, ps = self._tick(
                    self.params, patch_embed, x, states, text,
                    jnp.asarray(steps), jnp.asarray(mode_tab),
                    jnp.asarray(id_tab), jnp.asarray(dt),
                    jnp.asarray(nsteps), jnp.asarray(active),
                    jnp.asarray(reset))
            reset[:] = False
            if self.sync_every_tick:
                jax.block_until_ready(x)
            hist.append((dens, ps))
            act_log.append(active.copy())
            log = []
            now = time.perf_counter() - t0
            for w in range(W):
                if not active[w]:
                    continue
                req = lane_req[w]
                kind = MODE_NAMES[int(mode_tab[w, steps[w]])]
                log.append((w, req.rid, int(steps[w]), kind))
                steps[w] += 1
                if steps[w] >= req.num_steps:
                    # Slice the shape-bucket pad back off (no-op when the
                    # request filled its lane).
                    pending_out.append((req.rid, x[w][:, :req.x0.shape[1]]))
                    results[req.rid] = _result(None, [], req.arrival, now)
                    active[w], lane_req[w] = False, None
            tick_log.append(log)
            ticks += 1

        # ONE host sync for outputs + the whole per-lane metric history.
        outs = jax.device_get([o for _, o in pending_out])
        for (rid, _), o in zip(pending_out, outs):
            results[rid]["out"] = np.asarray(o)
        if hist:
            dens_h = np.asarray(jax.device_get(jnp.stack(
                [d for d, _ in hist])))
            ps_h = np.asarray(jax.device_get(jnp.stack(
                [p for _, p in hist])))
        else:
            dens_h = ps_h = np.zeros((0, W), np.float32)
        for t_idx, log in enumerate(tick_log):
            for w, rid, step, kind in log:
                results[rid]["trace"].append({
                    "step": step, "kind": kind,
                    "density": float(dens_h[t_idx, w]),
                    "pair_sparsity": float(ps_h[t_idx, w])})
        act_h = (np.stack(act_log) if act_log
                 else np.zeros((0, W), bool))
        return results, ticks, grouped_ticks, dens_h, ps_h, act_h

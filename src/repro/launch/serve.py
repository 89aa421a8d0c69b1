"""Serving launcher.

Two serving kinds, matching the paper's domain and the LM shape grid:

  * ``--kind diffusion`` — text-to-vision requests through the FlashOmni
    Update–Dispatch sampler (the paper's deployment scenario), driven by
    the :mod:`repro.launch.batching` request queue in one of three modes:

      - ``--serving sequential`` — one request at a time (baseline; the
        pipeline's LRU sampler cache still shares compiled samplers
        across same-config requests);
      - ``--serving stacked``    — same-shape/same-schedule requests
        stack on the batch axis into ONE cached single-scan sampler call
        (bit-identical per-lane outputs);
      - ``--serving continuous`` — mixed-schedule requests interleave in
        a fixed-width microbatch; lanes retire and refill without
        recompiling (a fixed ≤ 4 executable budget per lane shape).
        Mode-homogeneous ticks fold same-mode lanes into the model
        batch axis (``ContinuousBatcher(grouped="auto")``), so a
        homogeneous request mix serves at stacked-level throughput.
        ``--shape-buckets`` rounds near-miss ``N_v`` resolutions up to
        canonical lane sizes so they share one lane executable; the
        resulting lane-bucket map is printed after the run.

    ``--arrival-interval`` simulates request arrivals (seconds between
    requests); latencies are measured against arrival times.
  * ``--kind lm``        — LM prefill + decode loop with KV caches.

By default both run smoke configs on any backend.  ``--full`` serves the
published widths at the published token count, with bf16 weights and
compute, through the Pallas kernels compiled for the TPU
(``interpret=False``): on a machine without a TPU it fails rather than
fall back.  ``--layers`` cuts the depth to what one chip holds."""

from __future__ import annotations

import argparse
import dataclasses
import functools
import os
import time
from pathlib import Path
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec

from repro.configs.registry import arch_shapes, get_config, get_smoke
from repro.core.engine import EngineConfig
from repro.core.masks import MaskConfig
from repro.core.schedule import available_schedules
from repro.core.strategy import available_strategies
from repro.launch.batching import (ContinuousBatcher, Request,
                                   run_sequential, run_stacked)
from repro.models import dit as ditmod
from repro.models.registry import get_model


def enable_compile_cache() -> str:
    """Keep JAX's persistent compilation cache at a fixed path.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it itself and no
    other directory is set here.  Otherwise the cache goes to
    ``<repo>/.jax_cache`` (git-ignored): a fixed path, since the
    directory is part of what a later run must find again.  Called by
    entry points only, never at import."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(Path(__file__).resolve().parents[3] / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path


def init_weights(cfg, dtype, sharding=None):
    """Seeded DiT weights made on the device by ONE jitted program whose
    outputs are already ``dtype``: no f32 copy of the stack and no
    per-block list is ever held in device memory.  ``sharding`` places
    the outputs (e.g. replicated over an engine mesh) as they are made."""
    init = functools.partial(ditmod.init_params, cfg, dtype=dtype)
    return jax.jit(init, out_shardings=sharding)(jax.random.PRNGKey(0))


def serve_diffusion(arch: str, *, smoke: bool = True, num_requests: int = 2,
                    batch: Optional[int] = None,
                    n_vision: Optional[int] = None, num_steps: int = 12,
                    strategy: str = "flashomni", schedule: str = None,
                    serving: str = "sequential", lanes: int = 4,
                    arrival_interval: float = 0.0, mixed_steps: bool = False,
                    mixed_shapes: bool = False, shape_buckets=None,
                    mesh: tuple = (1, 1), layers: Optional[int] = None,
                    backend: Optional[str] = None,
                    stats: Optional[dict] = None):
    """Queue-driven diffusion serving (see module docstring for modes).

    ``smoke`` picks the tiny CPU config: f32, CPU-sized tiles (16/16,
    pool 32), 96 vision tokens, batch 2 and the XLA backend.  Otherwise
    the published config runs at its published vision length
    (``arch_shapes``), the ``MaskConfig`` tiles (64/64, pool 128), batch
    1, bf16 weights and compute, and the Pallas kernels compiled for the
    TPU.  Batch 1 because the CSR attention kernel prefetches its per-row
    KV lists into scalar memory (1 MiB on a v5e): at flux length they
    take 458 KB for batch 1 and overflow at batch 4.  ``layers`` cuts
    the depth (``n_layers``); ``backend`` overrides the engine backend
    (e.g. ``"xla"`` as the reference of a Pallas run).  ``stats`` (a
    dict, sequential serving only) receives :func:`pipeline.sample`'s
    stats of the last request.

    ``schedule`` names a registered SparsitySchedule preset (e.g.
    ``hunyuan-1.5x``, ``step-ramp``); it overrides the per-step mapping of
    ``strategy``.  ``mixed_steps`` alternates request step counts
    (``num_steps`` and ``3·num_steps//4``) to exercise the continuous
    batcher's mixed-length lane interleaving.  ``mixed_shapes`` alternates
    request vision lengths (``n_vision`` and ``n_vision − pool``) to
    exercise the continuous batcher's shape-bucketed lane partitioning;
    ``shape_buckets`` passes the canonical N_v bucket sizes through to
    :class:`~repro.launch.batching.ContinuousBatcher` (default when
    ``mixed_shapes``: ``(n_vision,)`` so the near-miss shape folds in).
    ``mesh`` is ``(dp, sp)``: with ``sp > 1`` the engine runs plan-sharded
    dispatch over a ``(data, seq)`` device mesh (``distributed/plan_shard``)
    — the Update step emits per-shard CSR partitions and attention
    exchanges only plan-live KV blocks.  Needs ``dp·sp`` local devices.
    Returns the per-request result dict from :mod:`repro.launch.batching`.
    """
    mask = dict(tau_q=0.5, tau_kv=0.15, interval=4, order=1, degrade=0.3,
                warmup_steps=2)
    if smoke:
        cfg = get_smoke(arch)
        n_vision = n_vision or 96
        batch = batch or 2
        mask.update(block_q=16, block_kv=16, pool=32)
        dtype, engine = jnp.float32, dict(backend=backend or "xla")
    else:
        cfg = get_config(arch)
        n_vision = n_vision or arch_shapes(cfg)[0].seq_len - cfg.n_text_tokens
        batch = batch or 1
        dtype = jnp.bfloat16
        engine = dict(backend=backend or "pallas", interpret=False)
    if layers is not None:
        cfg = dataclasses.replace(cfg, n_layers=layers)
    ecfg = EngineConfig(mask=MaskConfig(**mask), strategy=strategy,
                        mesh_dp=mesh[0], mesh_sp=mesh[1], **engine)
    sharding = None
    if mesh != (1, 1):
        # Weights replicate over the engine mesh as they are made; left
        # unplaced they would all land on the first device.
        from repro.launch.mesh import make_engine_mesh
        sharding = NamedSharding(make_engine_mesh(*mesh), PartitionSpec())
    params = init_weights(cfg, dtype, sharding)
    label = schedule or strategy

    requests = []
    for req in range(num_requests):
        # One PRNG key per request, SPLIT between noise and text: reusing
        # a single key for both (the old behaviour) correlates the noise
        # latents with the text embeddings sample-for-sample.
        kx, kt = jax.random.split(
            jax.random.fold_in(jax.random.PRNGKey(100), req))
        nv = n_vision
        if mixed_shapes and req % 2:
            nv = max(n_vision - ecfg.mask.pool, ecfg.mask.pool)
        x0 = jax.random.normal(kx, (batch, nv, cfg.patch_dim))
        text = jax.random.normal(kt, (batch, cfg.n_text_tokens, cfg.d_model))
        steps = num_steps
        if mixed_steps and req % 2:
            steps = max(3 * num_steps // 4, 1)
        requests.append(Request(rid=req, x0=x0, text_emb=text,
                                num_steps=steps, schedule=schedule,
                                arrival=req * arrival_interval))

    t0 = time.time()
    extra = ""
    if serving == "continuous":
        if shape_buckets is None and mixed_shapes:
            shape_buckets = (n_vision,)
        batcher = ContinuousBatcher(params, cfg, ecfg, lanes=lanes,
                                    scfg_dtype=dtype,
                                    shape_buckets=shape_buckets)
        batcher.submit_all(requests)
        results = batcher.run()
        extra = (f"  executables {batcher.stats['executables']}"
                 f"  ticks {batcher.stats['ticks']}"
                 f" ({batcher.stats['grouped_ticks']} grouped"
                 f"/{batcher.stats['scan_ticks']} scan)")
        # Lane-bucket map: which admitted shape folded into which lane
        # shape (ISSUE 6 — shape-bucketed serving lanes).
        print(f"[serve] lane shape buckets "
              f"({batcher.stats['shape_partitions']} partition(s)):")
        for orig, canon in sorted(batcher.stats["shape_buckets"].items()):
            fold = "=" if orig == canon else "->"
            print(f"[serve]   x0 {orig[0]} {fold} lane {canon[0]}")
    elif serving == "stacked":
        results = run_stacked(params, cfg, ecfg, requests, scfg_dtype=dtype)
    elif serving == "sequential":
        results = run_sequential(params, cfg, ecfg, requests,
                                 scfg_dtype=dtype, stats=stats)
    else:
        raise ValueError(f"unknown serving mode {serving!r}; expected "
                         "sequential | stacked | continuous")
    wall = time.time() - t0

    for req in requests:
        r = results[req.rid]
        dens = [s["density"] for s in (r["trace"] or [])
                if s["kind"] == "dispatch"]
        dtxt = (f"mean dispatch density "
                f"{sum(dens) / len(dens):.3f}  " if dens else "")
        print(f"[serve] req {req.rid} [{label}] ({serving}): "
              f"{req.num_steps} steps, latency {r['latency']:.2f}s  "
              f"{dtxt}out {r['out'].shape} "
              f"finite={bool(jnp.isfinite(r['out']).all())}")
    print(f"[serve] {serving}: {len(requests)} requests in {wall:.2f}s "
          f"({len(requests) / max(wall, 1e-9):.2f} req/s){extra}")
    return results


def serve_lm(arch: str, *, smoke: bool = True, batch: int = 2,
             prompt_len: int = 32, gen_len: int = 16, max_len: int = 64):
    cfg = get_smoke(arch) if smoke else get_config(arch)
    model = get_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    key = jax.random.PRNGKey(1)
    prompt = jax.random.randint(key, (batch, prompt_len), 0, cfg.vocab)
    cache = model.init_cache(batch, max_len, dtype=jnp.float32)
    decode = jax.jit(lambda p, c, tok, pos: model.decode_step(
        p, c, tok, pos, dtype=jnp.float32))

    t0 = time.time()
    # teacher-forced prefill through the decode path (smoke scale), then greedy
    tok = prompt[:, 0]
    for i in range(prompt_len - 1):
        logits, cache = decode(params, cache, prompt[:, i], jnp.int32(i))
    generated = []
    tok = prompt[:, -1]
    for i in range(gen_len):
        logits, cache = decode(params, cache, tok, jnp.int32(prompt_len - 1 + i))
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        generated.append(tok)
    dt = time.time() - t0
    gen = jnp.stack(generated, axis=1)
    print(f"[serve] {cfg.name}: prefill {prompt_len} + decode {gen_len} "
          f"in {dt:.2f}s -> tokens {gen[0][:8].tolist()}...")
    return gen


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--kind", default="lm", choices=["lm", "diffusion"])
    ap.add_argument("--full", action="store_true",
                    help="published widths and token count, bf16, Pallas "
                         "kernels compiled for the TPU (fails off-TPU)")
    ap.add_argument("--layers", type=int, default=None,
                    help="cut the diffusion model's depth to this many "
                         "blocks (e.g. what one chip holds)")
    ap.add_argument("--strategy", default="flashomni",
                    choices=available_strategies(),
                    help="sparse-symbol producer for --kind diffusion")
    ap.add_argument("--schedule", default=None,
                    choices=available_schedules(),
                    help="named SparsitySchedule preset (overrides the "
                         "--strategy per-step mapping)")
    ap.add_argument("--serving", default="sequential",
                    choices=["sequential", "stacked", "continuous"],
                    help="diffusion serving mode (see module docstring)")
    ap.add_argument("--requests", type=int, default=2)
    ap.add_argument("--lanes", type=int, default=4,
                    help="continuous-batcher microbatch width")
    ap.add_argument("--arrival-interval", type=float, default=0.0,
                    help="simulated seconds between request arrivals")
    ap.add_argument("--mixed-steps", action="store_true",
                    help="alternate request step counts (exercises "
                         "mixed-length lane interleaving)")
    ap.add_argument("--mixed-shapes", action="store_true",
                    help="alternate request vision lengths (exercises "
                         "shape-bucketed lane partitioning)")
    ap.add_argument("--shape-buckets", type=int, nargs="*", default=None,
                    help="canonical N_v lane bucket sizes for "
                         "--serving continuous (near-miss shapes round up)")
    ap.add_argument("--mesh", default="1,1", metavar="DP,SP",
                    help="engine mesh 'dp,sp' for --kind diffusion: sp>1 "
                         "runs plan-sharded dispatch over a (data, seq) "
                         "mesh, exchanging only plan-live KV blocks "
                         "(needs dp*sp local devices; e.g. --mesh 2,4 "
                         "under XLA_FLAGS=--xla_force_host_platform_"
                         "device_count=8)")
    args = ap.parse_args()
    try:
        mesh = tuple(int(p) for p in args.mesh.split(","))
        assert len(mesh) == 2 and mesh[0] >= 1 and mesh[1] >= 1
    except (ValueError, AssertionError):
        ap.error(f"--mesh expects 'dp,sp' positive ints, got {args.mesh!r}")
    enable_compile_cache()
    if args.kind == "diffusion":
        serve_diffusion(args.arch, smoke=not args.full,
                        strategy=args.strategy, schedule=args.schedule,
                        serving=args.serving, num_requests=args.requests,
                        lanes=args.lanes,
                        arrival_interval=args.arrival_interval,
                        mixed_steps=args.mixed_steps,
                        mixed_shapes=args.mixed_shapes,
                        shape_buckets=(tuple(args.shape_buckets)
                                       if args.shape_buckets else None),
                        mesh=mesh, layers=args.layers)
    else:
        serve_lm(args.arch, smoke=not args.full)


if __name__ == "__main__":
    main()

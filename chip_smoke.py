"""Bring-up check: serve flux-mmdit at its published width on a TPU.

Drives the normal serving entry point (``repro.launch.serve.
serve_diffusion``, sequential serving) at flux-mmdit's published widths
(d_model 3072, 24 heads x 128, d_ff 12,288; 512 text + 4,096 image
tokens) with random weights from a seed, bf16 weights and compute, and
the Pallas kernels compiled for the chip.  It checks what comes out and
compares it with the XLA engine path on the same chip.

    python chip_smoke.py            one chip: 2 requests of 8 steps, then
                                    the first request through Pallas and
                                    through XLA at a depth both fit
    python chip_smoke.py --chips 4  plan-sharded Dispatch over a (1, 4)
                                    mesh against the same request on one
                                    device, and nothing else

The last line of stdout is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Without a TPU, or when any phase fails, the script exits non-zero and
prints no such line.  The times it prints are bring-up readings (set-up
and compile included), not benchmark metrics.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

import numpy as np

ARCH = "flux-mmdit"
# Depth cut for one 16 GiB v5e: 38 blocks published here, 25 on this chip.
# The whole sampler (pipeline.build_sampler's `run`: batch 1, 4,608
# tokens, 8 steps, bf16, Pallas) compiled for a described v5e needs
# 0.40 GB of arguments per block (0.34 GB of bf16 weights, 0.057 GB of
# engine state) and about 0.2 GB of temporaries per block over a fixed
# ~2 GB.  Arguments + outputs + temporaries - aliased:
#   25 blocks: 9.99 + 1.43 + 5.04 - 1.43 GB = 14.00 GiB, under 14.4 GiB
#              (10% of 16 GiB kept free);
#   26 blocks: 14.48 GiB, over it; 28 blocks do not fit the chip at all.
LAYERS = 25
# The XLA engine path gathers each live row's KV blocks, about 3.9 GB of
# temporaries per block step (bf16 blocks + int32 gather indices) that the
# Pallas kernel never builds.  By the same sum its sampler takes 13.78 GiB
# at 16 blocks and 14.84 GiB at 18 (over the 10%-free line); 22 blocks do
# not fit at all.  Pallas and XLA are compared at 16 blocks.
REF_LAYERS = 16
STEPS = 8
REQUESTS = 2
# Pallas vs XLA (and mesh vs one device): both runs compute in bf16, which
# keeps 8 significant bits, and the kernels accumulate in another order
# than XLA's fused ops, so the two round differently at every block of
# every step.  A structural fault (a wrong or dropped tile) moves outputs
# by O(1); rounding drift stays well below these bounds.
TOL_REL_L2 = 1e-2      # ||a - b|| / ||b|| over the output latents
TOL_MAX_ABS = 0.25     # largest elementwise |a - b| (latents are ~N(0, 1))


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def fail(msg: str) -> None:
    print(f"[chip_smoke] FAIL: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


def tpu_devices(chips: int):
    """The TPU devices, or exit: this check never falls back to the CPU."""
    import jax
    try:
        devices = jax.devices()
    except RuntimeError as e:
        fail(f"JAX could not start a backend: {e}")
    if devices[0].platform != "tpu":
        fail(f"no TPU: JAX runs on {devices[0].platform!r} "
             f"({devices[0].device_kind})")
    if len(devices) < chips:
        fail(f"--chips {chips} needs {chips} TPU devices, found "
             f"{len(devices)}")
    return devices


def import_repo():
    src = Path(__file__).resolve().parent / "src"
    if not (src / "repro").is_dir():
        fail(f"{src / 'repro'} not found: run this from a checkout of "
             "the repository")
    sys.path.insert(0, str(src))


def compare(name: str, a: np.ndarray, b: np.ndarray) -> None:
    d = a.astype(np.float64) - b.astype(np.float64)
    max_abs = float(np.abs(d).max())
    rel = float(np.linalg.norm(d) / np.linalg.norm(b))
    log(f"{name}: max |diff| {max_abs!r}, relative L2 {rel!r}, "
        f"bit-identical {bool(np.array_equal(a, b))} "
        f"(tolerance: max {TOL_MAX_ABS}, relative {TOL_REL_L2})")
    if not (max_abs <= TOL_MAX_ABS and rel <= TOL_REL_L2):
        fail(f"{name} differs beyond tolerance")


def check_request(rid, r, n_vision: int, patch_dim: int) -> None:
    out = r["out"]
    if out.shape != (1, n_vision, patch_dim):
        fail(f"request {rid}: output shape {out.shape}")
    finite = bool(np.isfinite(out).all())
    kinds = [s["kind"] for s in r["trace"]]
    dens = [round(s["density"], 4) for s in r["trace"]
            if s["kind"] == "dispatch"]
    log(f"request {rid}: steps {kinds}; density per Dispatch step {dens}; "
        f"output {out.shape} finite={finite}")
    if not finite:
        fail(f"request {rid}: non-finite output")
    if kinds.count("update") < 1 or kinds.count("dispatch") < 3:
        fail(f"request {rid}: needs >= 1 Update and >= 3 Dispatch steps")


def one_chip(serve, cfg, n_vision, device) -> None:
    stats: dict = {}
    t0 = time.perf_counter()
    res = serve(REQUESTS, stats=stats)
    wall = time.perf_counter() - t0
    finish = [res[i]["finish"] for i in range(REQUESTS)]
    log(f"set-up reading: serve_diffusion call {wall:.2f}s in all "
        f"(weights + compile + {REQUESTS} requests); request 0 took "
        f"{finish[0]:.2f}s including the sampler compile, request 1 "
        f"{finish[1] - finish[0]:.2f}s warm")
    for rid in range(REQUESTS):
        check_request(rid, res[rid], n_vision, cfg.patch_dim)
    t0 = time.perf_counter()
    hlo = stats.pop("lower")().compile().as_text()
    n_kernels = hlo.count("tpu_custom_call")
    log(f"compiled sampler: {n_kernels} tpu_custom_call in its HLO "
        f"(re-lowered in {time.perf_counter() - t0:.2f}s)")
    if n_kernels == 0:
        fail("the compiled sampler holds no Pallas kernel")
    del stats, hlo
    mem = device.memory_stats() or {}
    log(f"peak HBM {mem.get('peak_bytes_in_use', 0) / 2**30:.3f} GiB of "
        f"{mem.get('bytes_limit', 0) / 2**30:.3f} GiB")
    pallas = serve(1, layers=REF_LAYERS)
    xla = serve(1, layers=REF_LAYERS, backend="xla")
    compare(f"Pallas vs XLA at {REF_LAYERS} blocks, request 0",
            pallas[0]["out"], xla[0]["out"])


def four_chips(serve, cfg, n_vision) -> None:
    one = serve(1)
    check_request(0, one[0], n_vision, cfg.patch_dim)
    mesh = serve(1, mesh=(1, 4))
    check_request(0, mesh[0], n_vision, cfg.patch_dim)
    compare("mesh (1, 4) vs one device, request 0", mesh[0]["out"],
            one[0]["out"])


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: plan-sharded mesh phase only")
    args = ap.parse_args()
    devices = tpu_devices(args.chips)
    import_repo()
    from repro.configs.registry import arch_shapes, get_config
    from repro.launch.serve import enable_compile_cache, serve_diffusion

    log(f"compile cache: {enable_compile_cache()}")
    cfg = dataclasses.replace(get_config(ARCH), n_layers=LAYERS)
    n_tokens = arch_shapes(cfg)[0].seq_len
    n_vision = n_tokens - cfg.n_text_tokens
    d = devices[0]
    log(f"device {d.platform} {d.device_kind} x{len(devices)}")
    log(f"{ARCH}: d_model {cfg.d_model}, {cfg.n_heads} x {cfg.hd} heads, "
        f"d_ff {cfg.d_ff}, {cfg.n_layers} of "
        f"{get_config(ARCH).n_layers} blocks, {n_tokens} tokens "
        f"({cfg.n_text_tokens} text + {n_vision} image), batch 1, "
        f"{STEPS} steps")

    def serve(n, layers=LAYERS, **kw):
        return serve_diffusion(ARCH, smoke=False, num_requests=n,
                               num_steps=STEPS, serving="sequential",
                               layers=layers, **kw)

    if args.chips == 4:
        four_chips(serve, cfg, n_vision)
    else:
        one_chip(serve, cfg, n_vision, d)
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()

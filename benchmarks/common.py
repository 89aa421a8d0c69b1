"""Benchmark utilities: wall-clock timing of jitted callables + FLOP
accounting helpers shared across the paper-figure benchmarks."""

from __future__ import annotations

import time
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["time_fn", "psnr", "flops_of", "static_flops_of",
           "check_flops_agreement", "GEMM_O_THEORY"]


def time_fn(fn: Callable, *args, iters: int = 5, warmup: int = 2) -> float:
    """Median wall-clock seconds of a jitted fn (block_until_ready)."""
    for _ in range(warmup):
        out = fn(*args)
        jax.block_until_ready(out)
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        out = fn(*args)
        jax.block_until_ready(out)
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts))


def psnr(a, b) -> float:
    a = np.asarray(a, np.float32)
    b = np.asarray(b, np.float32)
    mse = float(np.mean((a - b) ** 2))
    rng = float(np.max(np.abs(b))) or 1.0
    return 10 * np.log10(rng * rng / max(mse, 1e-12))


def flops_of(fn, *args) -> float:
    """Per-device HLO FLOPs of a jitted callable (cost analysis)."""
    c = jax.jit(fn).lower(*args).compile().cost_analysis()
    return float(c.get("flops", 0.0))


def static_flops_of(fn, *args) -> float:
    """FLOPs of ``fn`` from the STATIC cost model — no compilation.

    Counts the traced jaxpr with
    :func:`repro.analysis.cost_model.cost_of_jaxpr` (the interpreter the
    invariant analyzer certifies), giving an XLA-independent second
    opinion on :func:`flops_of` for the roofline rows.
    """
    from repro.analysis.cost_model import cost_of_jaxpr
    return float(cost_of_jaxpr(jax.make_jaxpr(fn)(*args)).flops)


def check_flops_agreement(name: str, measured: float, static: float,
                          rtol: float = 0.15) -> float:
    """Assert the XLA ``cost_analysis()`` FLOPs and the static model agree.

    Returns the static count so callers can record it in a derived row.
    XLA occasionally folds a handful of scalar ops the model counts (and
    vice versa for fused masking), so the tolerance is loose-ish; a real
    drift — a missing primitive handler or an op XLA started billing —
    lands far outside 15%.
    """
    if measured <= 0 or static <= 0:
        raise AssertionError(
            f"{name}: non-positive flops (measured={measured}, "
            f"static={static}) — one of the counters went vacuous")
    rel = abs(measured - static) / measured
    if rel > rtol:
        raise AssertionError(
            f"{name}: static cost model ({static:.3e}) disagrees with "
            f"XLA cost_analysis ({measured:.3e}) by {rel:.1%} (> {rtol:.0%})")
    return static


def GEMM_O_THEORY(n_interval: int, s: float) -> float:
    """Paper A.1.2: window speedup = 𝒩 / (1 + (𝒩−1)(1−s))."""
    return n_interval / (1.0 + (n_interval - 1) * (1.0 - s))

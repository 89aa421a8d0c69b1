"""Arithmetic shared by the metric readers in ``chipbench/metrics``."""

from __future__ import annotations

import numpy as np

from chipbench import trace as T
from chipbench import work as W


def psnr(a, b) -> float:
    """PSNR of ``a`` against ``b``, the peak being ``max |b|``."""
    a = np.asarray(a, np.float32)
    b = np.asarray(b, np.float32)
    mse = float(np.mean((a - b) ** 2))
    rng = float(np.max(np.abs(b))) or 1.0
    return 10 * np.log10(rng * rng / max(mse, 1e-12))


def steps_of(run, kind: str) -> list:
    """Per-step program counters of every traced request's ``kind`` steps."""
    return [st for r in run.records for st in r["trace"] if st["kind"] == kind]


def device_seconds(run, key: str, value) -> float:
    """Device time of the operations with ``ops[name][key] == value``,
    averaged over the chips."""
    per = [T.time_by(ev, run.ops, key).get(value, 0.0)
           for ev in run.device_events()]
    return sum(per) / len(per) / 1e9


def mode_ms_per_step(run, mode: str):
    n = len(steps_of(run, mode))
    seconds = device_seconds(run, "mode", mode)
    if not n or seconds <= 0:
        return None
    return seconds / n * 1e3


def kernel_roofline(run, kernel: str):
    """Percent of the roofline: the least time the chip could take for the
    kernel's work (the larger of the FLOP and the byte bound) over its
    summed device time."""
    seconds = device_seconds(run, "kernel", kernel)
    if seconds <= 0:
        return None
    flops = byts = 0.0
    for st in steps_of(run, "dispatch"):
        f, b = W.kernel_work(run.sizes, kernel, st["density"],
                             1.0 - st["pair_sparsity"], run.mesh)
        flops += f * run.n_layers
        byts += b * run.n_layers
    peak = run.peaks()
    return 100.0 * max(flops / peak["bf16_flops_per_s"],
                       byts / peak["hbm_bytes_per_s"]) / seconds

"""Whole-step share of the chips' peak (%): the operations of the traced
requests (chipbench.work, at the program's live fractions) over the
traced window times the peak times the chips."""

from chipbench import work as W


def read(run):
    flops = sum(W.request_flops(run.sizes, run.n_layers, r["trace"])
                for r in run.records)
    peak = run.peaks()["bf16_flops_per_s"]
    return 100.0 * flops / (run.window_s * peak * len(run.devices))

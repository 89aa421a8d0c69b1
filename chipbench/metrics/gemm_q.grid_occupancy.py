"""Percent of GEMM-Q's row slots that project a live row block, over the
Dispatch steps of the traced requests (the program's per-step live-work
counters)."""

from chipbench.readers import steps_of
from chipbench.scopes import live_share


def read(run):
    return live_share(steps_of(run, "dispatch"), "gemm_q_rows")

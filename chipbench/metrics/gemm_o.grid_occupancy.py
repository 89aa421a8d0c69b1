"""Percent of GEMM-O's (row, head) slots that reduce a live head, over the
Dispatch steps of the traced requests (the program's per-step live-work
counters)."""

from chipbench.readers import steps_of
from chipbench.scopes import live_share


def read(run):
    return live_share(steps_of(run, "dispatch"), "gemm_o_heads")

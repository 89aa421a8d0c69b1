"""Percent of the CSR attention kernel's grid steps that compute a live
(q-block, kv-block) tile, over the Dispatch steps of the traced requests
(the program's per-step live-work counters)."""

from chipbench.readers import steps_of
from chipbench.scopes import live_share


def read(run):
    return live_share(steps_of(run, "dispatch"), "csr_tiles")

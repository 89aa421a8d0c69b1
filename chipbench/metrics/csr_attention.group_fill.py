"""Percent of the CSR attention walk's fused online-softmax updates that
hold a live KV block: live tiles over ``G`` times the updates the walk
runs (``G`` the blocks one update takes), over the Dispatch steps of the
traced requests (the program's ``csr_groups`` counters).  A program
without that counter gives no reading."""

from chipbench.readers import steps_of
from chipbench.scopes import live_share


def read(run):
    steps = [st for st in steps_of(run, "dispatch")
             if "csr_groups" in st.get("grid", {})]
    return live_share(steps, "csr_groups")

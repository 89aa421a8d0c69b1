"""The fill of the CSR walk's fused updates, read from the program's
per-step ``csr_groups`` counters, and nothing from a program without
them."""

from chipbench import bench as B

STEPS = [
    {"kind": "update",
     "live": {"csr_tiles": 8, "csr_groups": 8},
     "grid": {"csr_tiles": 8, "csr_groups": 16}},
    {"kind": "dispatch",
     "live": {"csr_tiles": 6, "csr_groups": 6},
     "grid": {"csr_tiles": 6, "csr_groups": 8}},
    {"kind": "dispatch",
     "live": {"csr_tiles": 9, "csr_groups": 9},
     "grid": {"csr_tiles": 9, "csr_groups": 16}},
]


def _run(steps):
    return type("Run", (), {"records": [{"trace": steps}]})()


def test_group_fill_reads_the_dispatch_steps():
    for name in ("csr_attention.group_fill",
                 "csr_attention.group_fill.hunyuan"):
        assert B.load_reader(name)(_run(STEPS)) == 100.0 * 15 / 24


def test_group_fill_reads_nothing_without_the_counter():
    older = [{k: ({c: n for c, n in v.items() if c != "csr_groups"}
                  if k in ("live", "grid") else v) for k, v in st.items()}
             for st in STEPS]
    bare = [{"kind": st["kind"]} for st in STEPS]
    for steps in (older, bare, []):
        assert B.load_reader("csr_attention.group_fill")(_run(steps)) is None

"""Attribution by the program's own names (``chipbench/scopes.py``) and the
one-request recorder (``chipbench/record.py``): on a small trace written
out by hand, with nested ``fo.*`` host spans, and through the recorder's
whole path at test size."""

import json

import jax
import pytest

from chipbench import bench as B
from chipbench import record
from chipbench import run as R
from chipbench import scopes as S
from chipbench import trace as T
from chipbench_cells import small_cell

_ON = "jit(run)/while/body/closed_call/cond"
HLO = f'''HloModule jit_run, is_scheduled=true

FileNames
1 "/src/repro/core/engine.py"
2 "/src/repro/kernels/flashomni_attention.py"

FunctionNames
1 "update_layer"
2 "dispatch_layer"
3 "flashomni_attention_csr"
4 "rms_norm"

FileLocations
1 {{file_name_id=1 function_name_id=1 line=1 end_line=1 column=1 end_column=2}}
2 {{file_name_id=1 function_name_id=2 line=2 end_line=2 column=1 end_column=2}}
3 {{file_name_id=2 function_name_id=3 line=3 end_line=3 column=1 end_column=2}}
4 {{file_name_id=1 function_name_id=4 line=4 end_line=4 column=1 end_column=2}}

StackFrames
1 {{file_location_id=1 parent_frame_id=1}}
2 {{file_location_id=2 parent_frame_id=2}}
3 {{file_location_id=3 parent_frame_id=2}}
4 {{file_location_id=4 parent_frame_id=4}}

%body (p: bf16[8]) -> bf16[8] {{
  %fusion.1 = bf16[8]{{0}} fusion(%p), kind=kLoop, calls=%f1, metadata={{op_name="{_ON}/branch_1_fun/fo.update/while/body/fo.attention/dot_general" stack_frame_id=1}}
  %fusion.2 = bf16[8]{{0}} fusion(%p), kind=kLoop, calls=%f2, metadata={{op_name="{_ON}/branch_1_fun/fo.update/while/body/fo.plan/sort" stack_frame_id=1}}
  %fusion.3 = bf16[8]{{0}} fusion(%p), kind=kLoop, calls=%f3, metadata={{op_name="{_ON}/branch_1_fun/fo.update/while/body/fo.mlp/gelu" stack_frame_id=1}}
  %fusion.4 = bf16[8]{{0}} fusion(%p), kind=kLoop, calls=%f4, metadata={{op_name="{_ON}/branch_1_fun/fo.update/while/body/dynamic_update_slice" stack_frame_id=1}}
  %flashomni_csr_attention.5 = bf16[8]{{0}} custom-call(%p), custom_call_target="tpu_custom_call", frontend_attributes={{kernel_metadata={{}}}}, metadata={{op_name="{_ON}/branch_2_fun/fo.dispatch/while/body/fo.attention/flashomni_csr_attention/pallas_call" stack_frame_id=3}}
  %fusion.6 = bf16[8]{{0}} fusion(%p), kind=kLoop, calls=%f6, metadata={{op_name="{_ON}/branch_2_fun/fo.dispatch/while/body/fo.qkv/dot_general" stack_frame_id=4}}
  %fusion.7 = bf16[8]{{0}} fusion(%p), kind=kLoop, calls=%f7, metadata={{op_name="{_ON}/branch_2_fun/fo.dispatch/fo.io/dot_general" stack_frame_id=2}}
  %fusion.8 = bf16[8]{{0}} fusion(%p), kind=kLoop, calls=%f8, metadata={{op_name="jit(run)/while/body/fo.io/add"}}
  ROOT %while.9 = bf16[8]{{0}} while(%p), condition=%c, body=%b
}}
'''

# (name, start_ns, duration_ns) on one device, two steps: one Update
# (0-40) and one Dispatch (50-90), then the Euler update.
EVENTS = [
    ("while.9", 0, 100),                       # control flow
    ("fusion.1", 0, 10),                       # update: attention
    ("fusion.2", 10, 5),                       # update: plan
    ("fusion.3", 15, 20),                      # update: mlp
    ("fusion.4", 35, 5),                       # update: the layer scan's own
    ("flashomni_csr_attention.5", 50, 20),     # dispatch: attention (kernel)
    ("fusion.6", 70, 10),                      # dispatch: qkv
    ("fusion.7", 80, 10),                      # dispatch: io
    ("fusion.8", 92, 3),                       # outside the steps
]
# Host spans nest as the program writes them: a request holds the launch,
# the wait and the fetch; the benchmark's own span holds the request.
SPANS = [
    ("bench.serve", 0, 100, {}),
    ("fo.request", 0, 98, {"rid": 1}),
    ("fo.launch", 0, 2, {"compiled": 0}),
    ("fo.wait", 2, 94, {}),
    ("fo.fetch", 96, 2, {}),
]
STEPS = [
    {"kind": "update", "density": 1.0, "pair_sparsity": 0.0,
     "live": {"gemm_q_rows": 4, "csr_tiles": 8, "gemm_o_heads": 8},
     "grid": {"gemm_q_rows": 4, "csr_tiles": 8, "gemm_o_heads": 8}},
    {"kind": "dispatch", "density": 0.5, "pair_sparsity": 0.6,
     "live": {"gemm_q_rows": 3, "csr_tiles": 2, "gemm_o_heads": 4},
     "grid": {"gemm_q_rows": 4, "csr_tiles": 8, "gemm_o_heads": 8}},
]


@pytest.fixture
def scopes():
    return S.scope_ops(HLO)


def test_scope_of_takes_mode_innermost_part_and_kernel():
    assert S.scope_of(f"{_ON}/branch_2_fun/fo.dispatch/while/body/fo.mlp/"
                      "fo.attention/flashomni_gemm_o/pallas_call") == \
        {"mode": "dispatch", "part": "attention", "kernel": "flashomni_gemm_o"}
    assert S.scope_of("jit(run)/while/body/add") == \
        {"mode": None, "part": None, "kernel": None}
    # A program without the names (an older commit) yields none.
    assert S.scope_of(f"{_ON}/branch_1_fun/while/body/dot_general")["mode"] \
        is None


def test_parts_and_groups_per_step(scopes):
    events = T.leaf_events(EVENTS, T.hlo_ops(HLO))
    assert S.part_ns(events, scopes, "update") == \
        {"attention": 10, "plan": 5, "mlp": 20, None: 5}
    assert S.part_ns(events, scopes, "dispatch") == \
        {"attention": 20, "qkv": 10, "io": 10}
    assert S.group_ms_per_step(events, scopes, "update", 1) == \
        {"attention": 1e-5, "proj": 0.0, "engine": 5e-6, "mlp": 2e-5}
    assert S.group_ms_per_step(events, scopes, "dispatch", 2) == \
        {"attention": 1e-5, "proj": 5e-6, "engine": 0.0, "mlp": 0.0}
    # The parts agree with the step modes the stack frames give.
    by_mode = T.time_by(events, T.hlo_ops(HLO), "mode")
    assert by_mode == {"update": 40, "dispatch": 40, None: 3}
    assert S.kernel_ns(events, scopes) == {"csr_attention": 20}
    # Without scopes there is nothing to split: no reading, not zeros.
    assert S.group_ms_per_step(events, {}, "update", 1) == {}


def test_idle_time_is_named_by_the_innermost_span():
    events = T.leaf_events(EVENTS, T.hlo_ops(HLO))
    # Idle: 40-50 (inside fo.wait), 90-92 (fo.wait), 95-100 (the fetch
    # until 98, then bench.serve).
    assert S.idle_ns_in(events, SPANS, "fo.request") == [10 + 2 + 3]
    gaps = T.idle_gaps(events, [sp[:3] for sp in SPANS], (0, 100), k=3)
    assert gaps == [["fo.wait", 10e-9], ["fo.fetch", 5e-9],
                    ["fo.wait", 2e-9]]


def test_grid_occupancy_readers():
    run = type("Run", (), {"records": [{"trace": STEPS}]})()
    read = {k: B.load_reader(f"{k}.grid_occupancy")(run)
            for k in ("csr_attention", "gemm_q", "gemm_o")}
    assert read == {"csr_attention": 25.0, "gemm_q": 75.0, "gemm_o": 50.0}
    # A program that reports no live work gives no reading.
    old = [{k: v for k, v in st.items() if k not in ("live", "grid")}
           for st in STEPS]
    run.records = [{"trace": old}]
    for k in read:
        assert B.load_reader(f"{k}.grid_occupancy")(run) is None


def test_reduce_request(scopes):
    got = record.reduce_request(HLO, EVENTS, SPANS, STEPS, (0, 100))
    assert got["step_ms"] == {"update": 4e-5, "dispatch": 4e-5}
    assert got["group_ms"]["update"]["mlp"] == 2e-5
    assert got["occupancy"] == {"csr_tiles": 25.0, "gemm_q_rows": 75.0,
                                "gemm_o_heads": 50.0}
    assert got["idle_gaps"][0] == ["fo.wait", 10e-9]
    assert got["idle_ms_per_request"] == [15e-6]
    assert got["launch_compiled"] == [False]
    assert got["kernel_s"] == got["kernel_s_by_file"] == {
        "csr_attention": 2e-8}


def test_excerpt_keeps_what_the_reductions_read():
    full, scoped = T.hlo_ops(HLO), S.scope_ops(HLO)
    keep = {"fusion.1", "flashomni_csr_attention.5", "fusion.6", "while.9"}
    text = record.excerpt_hlo(HLO, keep)
    cut = T.hlo_ops(text)
    assert set(cut) == keep
    assert cut == {k: full[k] for k in keep}
    assert S.scope_ops(text) == {k: scoped[k] for k in keep}
    # fusion.6's stack is cut short before dispatch_layer: the excerpt
    # keeps, per branch, one instruction whose stack names the mode.
    cut = T.hlo_ops(record.excerpt_hlo(HLO, {"fusion.6"}))
    assert set(cut) == {"fusion.6", "flashomni_csr_attention.5", "fusion.1"}
    assert cut["fusion.6"] == full["fusion.6"]
    assert cut["fusion.6"]["mode"] == "dispatch"


def test_recorder_end_to_end_at_test_size(monkeypatch, tmp_path):
    """The recorder's whole path on the CPU: set-up, one traced request,
    the program's spans read back, the compiled program cut to an excerpt.
    The CPU's profile has no device plane, so one op stands in for it."""
    cell = small_cell()
    cell["model"]["engine"].update(backend="xla", interpret=True)
    monkeypatch.setattr(B, "find_cell", lambda bench, name: cell)
    monkeypatch.setattr(R, "require_tpu", lambda chips: jax.devices()[:1])

    def load_trace(trace_dir):
        spans = [sp[:3] for sp in S.load_spans(trace_dir, prefix="bench.")]
        return {"devices": {"/device:TPU:0": [("fusion.0", spans[0][1], 1.0)]},
                "spans": spans}

    monkeypatch.setattr(T, "load_trace", load_trace)
    monkeypatch.setattr(record, "TRACE_DIR", tmp_path / "trace")
    out = tmp_path / "out"
    assert record.main(["--workload", "flux.sparse.s28", "--seed",
                        str(2 ** 33 + 5), "--out", str(out)]) == 0
    summary = json.loads((out / "summary.json").read_text())
    assert sum(summary["steps"].values()) == 8
    assert summary["steps"]["update"] and summary["steps"]["dispatch"]
    assert summary["launch_compiled"] == [False]
    for name in ("fo.request", "fo.wait", "fo.fetch", "fo.launch",
                 "fo.metrics", "bench.serve"):
        assert summary["host_spans"][name][0] == 1, name
    assert all(0 < v <= 100 for v in summary["occupancy"].values())
    excerpt = json.loads((out / "excerpt.json").read_text())
    assert excerpt["events"] == [["fusion.0", 1, 1.0]]
    assert len(excerpt["steps"]) == 8
    assert "FileNames" in excerpt["hlo"]


# ---------------------------------------------------------------------------
# One request of the scoped program, recorded on a v5e by chipbench/record.py
# ---------------------------------------------------------------------------

SCOPED = json.loads((B.ROOT / "tests" / "chipbench" / "data" /
                     "recorded_request_scoped.json").read_text())


def _scoped_events():
    """The recorded ops laid end to end: (instruction, start, duration)."""
    events, t = [], 0.0
    for name, count, total in SCOPED["events"]:
        for _ in range(count):
            events.append((T.instruction(name), t, total / count))
            t += total / count
    return events


def test_recorded_scoped_request_keeps_the_old_attribution():
    ops = T.hlo_ops(SCOPED["hlo"])
    kernels = {op["kernel"]: op["mode"] for op in ops.values()
               if op["kernel"]}
    assert kernels == {"gemm_q": "dispatch", "csr_attention": "dispatch",
                       "gemm_o": "dispatch"}
    assert {op["mode"] for op in ops.values()} == {"update", "dispatch", None}
    # The kernels' names find the same kernels, with the same time.
    events = T.leaf_events(_scoped_events(), ops)
    by_file = {k: v for k, v in T.time_by(events, ops, "kernel").items() if k}
    assert S.kernel_ns(events, S.scope_ops(SCOPED["hlo"])) == by_file


@pytest.mark.parametrize("mode", ["update", "dispatch"])
def test_recorded_scoped_parts_make_up_the_step(mode):
    ops = T.hlo_ops(SCOPED["hlo"])
    events = T.leaf_events(_scoped_events(), ops)
    n = sum(st["kind"] == mode for st in SCOPED["steps"])
    step_ms = T.time_by(events, ops, "mode")[mode] / n / 1e6
    groups = S.group_ms_per_step(events, S.scope_ops(SCOPED["hlo"]), mode, n)
    assert set(groups) == set(S.GROUPS)
    assert 0.95 * step_ms <= sum(groups.values()) <= step_ms
    assert all(v > 0 for k, v in groups.items()
               if not (mode == "dispatch" and k == "engine"))


def test_recorded_scoped_grid_occupancy():
    steps = SCOPED["steps"]
    got = {k: S.live_share(steps, k)
           for k in ("csr_tiles", "gemm_q_rows", "gemm_o_heads")}
    assert all(0 < v <= 100 for v in got.values()), got
    # At cap_q_frac 1 the GEMM-O slots are the (row, head) pairs the
    # density counts: the two program counters agree.
    dispatch = [st for st in steps if st["kind"] == "dispatch"]
    density = sum(st["density"] for st in dispatch) / len(dispatch)
    assert got["gemm_o_heads"] == pytest.approx(100 * density, rel=1e-6)

"""The reduction from a device trace and the program's HLO text to the
per-layer numbers: on an excerpt recorded from one request on a v5e
(``data/recorded_request.json``), and on a small trace written out by hand
in the same format for the interval arithmetic."""

import json
from pathlib import Path

import pytest

from chipbench import readers
from chipbench import trace as T

RECORDED = json.loads(
    (Path(__file__).parent / "data" / "recorded_request.json").read_text())


def _recorded_events():
    """The recorded ops laid end to end: (instruction, start, duration)."""
    events, t = [], 0.0
    for name, count, total in RECORDED["events"]:
        for _ in range(count):
            events.append((T.instruction(name), t, total / count))
            t += total / count
    return events


def test_recorded_attribution():
    ops = T.hlo_ops(RECORDED["hlo"])
    assert [ops[f"closed_call.{i}"]["kernel"] for i in (72, 73, 74)] == \
        ["gemm_q", "csr_attention", "gemm_o"]
    assert {ops[f"closed_call.{i}"]["mode"] for i in (72, 73, 74)} == \
        {"dispatch"}
    assert ops["fusion.667"]["mode"] == "update"       # an Update-step MLP
    assert ops["fusion.632"]["mode"] == "dispatch"     # a Dispatch-step MLP
    assert ops["conditional.3"]["mode"] is None


def test_recorded_times_leave_out_control_flow():
    ops = T.hlo_ops(RECORDED["hlo"])
    events = T.leaf_events(_recorded_events(), ops)
    names = {n for n, _, _ in events}
    assert not names & {"while.72", "while.73", "conditional.3"}
    kernel = T.time_by(events, ops, "kernel")
    total = {T.instruction(n): d for n, _, d in RECORDED["events"]}
    assert kernel["csr_attention"] == pytest.approx(total["closed_call.73"])
    assert T.busy_ns(events) == pytest.approx(
        sum(d for n, _, d in events))


def test_recorded_roofline_is_a_share():
    """The CSR kernel's roofline share from the recorded request: 19
    Dispatch steps of 25 blocks at the densities the program reported."""
    ops = T.hlo_ops(RECORDED["hlo"])
    sizes = dict(d_model=3072, n_heads=24, head_dim=128, d_ff=12288,
                 patch_dim=64, n_text_tokens=512, n_image_tokens=4096)
    steps = [{"kind": "dispatch", "density": 0.78, "pair_sparsity": 0.3}] * 19
    run = type("Run", (), dict(
        ops=ops, sizes=sizes, n_layers=25, mesh=(1, 1),
        records=[{"trace": steps}],
        device_events=lambda self: [_recorded_events()],
        peaks=lambda self: {"bf16_flops_per_s": 197e12,
                            "hbm_bytes_per_s": 819e9}))()
    share = readers.kernel_roofline(run, "csr_attention")
    flops = 4 * 4608 ** 2 * 3072 * 0.7 * 25 * 19
    seconds = RECORDED["events"][2][2] / 1e9
    assert share == pytest.approx(100 * flops / 197e12 / seconds)
    assert 0 < share < 100
    assert readers.kernel_roofline(run, "no_such_kernel") is None


def test_instruction_name_of_an_event():
    assert T.instruction("%closed_call.73 = bf16[24,4608,128]{2,1,0} "
                         "custom-call(s32[24,72]{1,0} %copy-done.83)") == \
        "closed_call.73"
    assert T.instruction("fusion.1") == "fusion.1"

HLO = '''HloModule jit_run, is_scheduled=true

FileNames
1 "/src/repro/diffusion/pipeline.py"
2 "/src/repro/core/engine.py"
3 "/src/repro/kernels/gemm_q.py"
4 "/src/repro/kernels/flashomni_attention.py"
5 "/src/repro/kernels/gemm_o.py"
6 "/src/repro/models/dit.py"

FunctionNames
1 "build_sampler.<locals>.body"
2 "update_layer"
3 "dispatch_layer"
4 "gemm_q_sparse_kernel"
5 "flashomni_attention_csr"
6 "gemm_o_sparse_kernel"
7 "_block"

FileLocations
1 {file_name_id=1 function_name_id=1 line=120 end_line=121 column=1 end_column=2}
2 {file_name_id=2 function_name_id=2 line=499 end_line=499 column=1 end_column=2}
3 {file_name_id=2 function_name_id=3 line=564 end_line=564 column=1 end_column=2}
4 {file_name_id=3 function_name_id=4 line=106 end_line=106 column=1 end_column=2}
5 {file_name_id=4 function_name_id=5 line=160 end_line=160 column=1 end_column=2}
6 {file_name_id=5 function_name_id=6 line=121 end_line=121 column=1 end_column=2}
7 {file_name_id=6 function_name_id=7 line=211 end_line=211 column=1 end_column=2}

StackFrames
1 {file_location_id=1 parent_frame_id=1}
2 {file_location_id=2 parent_frame_id=1}
3 {file_location_id=3 parent_frame_id=1}
4 {file_location_id=4 parent_frame_id=3}
5 {file_location_id=5 parent_frame_id=3}
6 {file_location_id=6 parent_frame_id=3}
7 {file_location_id=7 parent_frame_id=1}

%region_2 (p: bf16[8]) -> bf16[8] {
  %fusion.1 = bf16[8]{0} fusion(%p), kind=kLoop, calls=%f1, metadata={op_name="jit(run)/while/body/cond/branch_1_fun/while/body/dot_general" stack_frame_id=2}
  %fusion.2 = bf16[8]{0} fusion(%p), kind=kLoop, calls=%f2, metadata={op_name="jit(run)/while/body/cond/branch_1_fun/while/body/gelu" stack_frame_id=7}
  %closed_call.3 = bf16[8]{0} custom-call(%p), custom_call_target="tpu_custom_call", metadata={op_name="jit(run)/while/body/cond/branch_2_fun/while/body/pallas_call" stack_frame_id=4}
  %closed_call.4 = bf16[8]{0} custom-call(%p), custom_call_target="tpu_custom_call", metadata={op_name="jit(run)/while/body/cond/branch_2_fun/while/body/pallas_call" stack_frame_id=5}
  %closed_call.5 = bf16[8]{0} custom-call(%p), custom_call_target="tpu_custom_call", metadata={op_name="jit(run)/while/body/cond/branch_2_fun/while/body/pallas_call" stack_frame_id=6}
  %all-to-all.6 = bf16[8]{0} all-to-all(%p), dimensions={0}, metadata={op_name="jit(run)/while/body/cond/branch_2_fun/while/body/all_to_all" stack_frame_id=3}
  %fusion.7 = bf16[8]{0} fusion(%p), kind=kLoop, calls=%f3, metadata={op_name="jit(run)/while/body/cond/branch_2_fun/while/body/gelu" stack_frame_id=7}
  ROOT %while.8 = bf16[8]{0} while(%p), condition=%c, body=%b
}
'''

# (name, start_ns, duration_ns) on one device; a 100 ns window from 0.
EVENTS = [
    ("while.8", 0, 100),          # control flow: spans its children
    ("fusion.1", 0, 10),          # update
    ("fusion.2", 10, 10),         # update (MLP)
    ("closed_call.3", 30, 5),     # gemm_q
    ("closed_call.4", 35, 20),    # csr attention
    ("all-to-all.6", 50, 15),     # 5 ns hidden under attention, 10 exposed
    ("closed_call.5", 70, 5),     # gemm_o
    ("fusion.7", 75, 5),          # dispatch (MLP)
]
SPANS = [("bench.serve", 0, 90), ("bench.record", 90, 10)]


@pytest.fixture
def ops():
    return T.hlo_ops(HLO)


def test_hlo_attribution(ops):
    assert ops["fusion.1"]["mode"] == "update"
    assert ops["fusion.2"]["mode"] == "update"      # MLP: by its branch
    assert ops["fusion.7"]["mode"] == "dispatch"
    assert [ops[f"closed_call.{i}"]["kernel"] for i in (3, 4, 5)] == \
        ["gemm_q", "csr_attention", "gemm_o"]
    assert ops["fusion.1"]["kernel"] is None
    assert ops["all-to-all.6"]["collective"]
    assert not ops["closed_call.4"]["collective"]
    assert ops["while.8"]["opcode"] == "while"


def test_busy_idle_and_time_by(ops):
    events = T.leaf_events(EVENTS, ops)
    assert "while.8" not in [e[0] for e in events]
    assert T.busy_ns(events) == 65                  # idle: 20-30, 65-70, 80-100
    by_mode = T.time_by(events, ops, "mode")
    assert by_mode == {"update": 20, "dispatch": 50}
    by_kernel = T.time_by(events, ops, "kernel")
    assert (by_kernel["gemm_q"], by_kernel["csr_attention"],
            by_kernel["gemm_o"]) == (5, 20, 5)


def test_exposed_collective(ops):
    assert T.exposed_collective_ns(T.leaf_events(EVENTS, ops), ops) == 10


def test_idle_gaps_are_named_by_host_span(ops):
    gaps = T.idle_gaps(T.leaf_events(EVENTS, ops), SPANS, (0, 100), k=2)
    assert gaps == [["bench.record", 20e-9], ["bench.serve", 10e-9]]


def test_union_and_intersect():
    a = T.union([(0, 10), (5, 10), (30, 5)])
    assert a == [(0, 15), (30, 35)]
    assert T.intersect(a, T.union([(10, 25)])) == 10

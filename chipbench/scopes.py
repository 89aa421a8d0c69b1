"""Attribution by the program's own names: ``fo.`` scopes, kernel names
and host spans.

The program puts every op of a denoising step under the step mode's named
scope (``fo.dense``, ``fo.update``, ``fo.dispatch``) and under one block
part (``fo.qkv``, ``fo.attention``, ``fo.o_proj``, ``fo.symbols``,
``fo.plan``, ``fo.cache``, ``fo.mlp``, ``fo.io``); the compiled module
keeps them in each instruction's ``op_name``.  Its Pallas kernels carry a
``name`` (``flashomni_*``), which ends up in the ``op_name`` as the
segment before ``pallas_call``.  Its host spans (``fo.request``,
``fo.wait``, ``fo.fetch``, ``fo.states``, ``fo.schedule``, ``fo.launch``,
``fo.metrics``) are ``jax.profiler.TraceAnnotation``s, on the profile's
clock beside the device operations.

``scope_ops`` reads the compiled text, ``load_spans`` the ``.xplane.pb``;
the functions below reduce them.  A program without these names (an
older commit) yields no mode or part for any op, and no span.
"""

from __future__ import annotations

import glob
import re
from collections import defaultdict

from chipbench import trace as T

MODES = {"fo.dense": "dense", "fo.update": "update",
         "fo.dispatch": "dispatch"}
PARTS = ("qkv", "attention", "o_proj", "symbols", "plan", "cache", "mlp",
         "io")
# Parts summed into each ``<mode>_ms.<group>`` metric; ``io`` and ops
# under no part make up the rest of a step.
GROUPS = {"attention": ("attention",), "proj": ("qkv", "o_proj"),
          "engine": ("symbols", "plan", "cache"), "mlp": ("mlp",)}
# Kernel names of the Dispatch path, as the per-layer metrics name them.
KERNELS = {"flashomni_csr_attention": "csr_attention",
           "flashomni_csr_attention_bucketed": "csr_attention",
           "flashomni_gemm_q": "gemm_q", "flashomni_gemm_o": "gemm_o",
           "flashomni_gemm_o_bucketed": "gemm_o"}

_INST = re.compile(r"^\s*(?:ROOT )?%([\w.\-]+) = ")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def scope_of(op_name: str) -> dict:
    """``{"mode", "part", "kernel"}`` of one ``op_name``: the step mode's
    scope, the innermost part scope and the named kernel, each ``None``
    where the name has none."""
    segs = op_name.split("/")
    mode = part = kernel = None
    for i, s in enumerate(segs):
        if s in MODES:
            mode = MODES[s]
        elif s.startswith("fo.") and s[3:] in PARTS:
            part = s[3:]
        elif s == "pallas_call" and i:
            kernel = segs[i - 1]
    return {"mode": mode, "part": part, "kernel": kernel}


def scope_ops(text: str) -> dict:
    """``{instruction: scope_of(op_name)}`` over the compiled module's
    text (``compiled.as_text()``)."""
    ops = {}
    for line in text.splitlines():
        m = _INST.match(line)
        if m:
            op_name = _OP_NAME.search(line)
            ops[m.group(1)] = scope_of(op_name.group(1) if op_name else "")
    return ops


def part_ns(events, scopes: dict, mode: str) -> dict:
    """Device time of ``mode``'s ops by part (``None``: under no part)."""
    out = defaultdict(float)
    for name, _, d in events:
        s = scopes.get(name)
        if s and s["mode"] == mode:
            out[s["part"]] += d
    return dict(out)


def group_ms_per_step(events, scopes: dict, mode: str, n_steps: int) -> dict:
    """``{group: ms per step}`` of ``mode`` (``GROUPS``), or ``{}`` where
    no op of the mode carries a part."""
    by_part = part_ns(events, scopes, mode)
    if not n_steps or not any(p for p in by_part if p is not None):
        return {}
    return {g: sum(by_part.get(p, 0.0) for p in parts) / n_steps / 1e6
            for g, parts in GROUPS.items()}


def kernel_ns(events, scopes: dict) -> dict:
    """Device time per Dispatch kernel, found by the kernels' names."""
    out = defaultdict(float)
    for name, _, d in events:
        kernel = KERNELS.get((scopes.get(name) or {}).get("kernel"))
        if kernel:
            out[kernel] += d
    return dict(out)


def load_spans(trace_dir: str, prefix: str = "fo.") -> list:
    """Host spans whose name starts with ``prefix``:
    ``[(name, start_ns, dur_ns, {arg: value})]`` on the device planes'
    clock."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    spans = []
    for plane in ProfileData.from_file(paths[-1]).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [(e.name, float(e.start_ns), float(e.duration_ns),
                           dict(e.stats)) for e in line.events
                          if e.name.startswith(prefix)]
    return spans


def idle_ns_in(events, spans, name: str) -> list:
    """Device-idle time inside each host span called ``name``, in order."""
    busy = T.union((s, d) for _, s, d, *_ in events)
    return [d - T.intersect(busy, [(s, s + d)])
            for n, s, d, *_ in sorted(spans, key=lambda sp: sp[1])
            if n == name]


def live_share(steps, kernel: str):
    """Percent of the launched grid slots that do live work, summed over
    the Dispatch steps of ``steps`` (the program's per-step counters), or
    ``None`` where the program reports none."""
    steps = [st for st in steps if st["kind"] == "dispatch" and "live" in st]
    grid = sum(st["grid"][kernel] for st in steps)
    if not grid:
        return None
    return 100.0 * sum(st["live"][kernel] for st in steps) / grid

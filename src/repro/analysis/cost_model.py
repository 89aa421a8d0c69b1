"""Symbolic per-primitive cost interpreter over traced jaxprs (ISSUE 10).

Walks an abstract trace (``jax.make_jaxpr`` output — no compilation, no
FLOPs executed) and accounts three resources per equation, recursing
into every sub-jaxpr with the trip-count multiplier of its enclosing
higher-order primitive:

* **flops** — ``dot_general``/``conv`` from the contraction shapes
  (2 flops per MAC, matching XLA ``cost_analysis()``), element-wise and
  reduction primitives at one flop per element, pure layout primitives
  (reshape / transpose / broadcast / convert / slice / pad / concat) at
  zero.
* **hbm bytes** — operand + result bytes per equation, with gather /
  dynamic-slice special-cased to *touched* bytes (result + indices, not
  the whole gathered operand) so a plan-capacity gather over a large KV
  buffer costs what it moves, not what it could address.  This is a
  pre-fusion upper-bound proxy, not an HLO buffer-assignment replay —
  useful for *scaling* certificates (is the byte count a function of
  live slots or of ``T_kv``?), not as an absolute HBM counter.
* **collective bytes** — per collective kind, both the *payload*
  (result bytes, the convention of the dry-run HLO-text parser, so the
  two accountings cross-check 1:1) and the *wire* bytes (what actually
  crosses links: ``(P-1)/P`` of an all-to-all, ``(P-1)/P`` of an
  all-gather result, twice that for a psum).  Axis sizes resolve from
  the enclosing ``shard_map`` mesh params (or the ``axis_sizes``
  argument for traces made under ``jax.pmap``-style outer binders).

Recursion rules: ``scan`` multiplies its body by ``length``;
``while_loop`` by 1 (trip count is dynamic — the estimate is a lower
bound there, recorded in :attr:`CostEstimate.inexact`); ``cond`` /
``switch`` take the per-resource **max** over branches; ``pallas_call``
takes the kernel's declared ``cost_estimate`` where it has one, else
multiplies its kernel body by the grid size; everything else
(``pjit``, ``custom_jvp/vjp``, ``remat``, ``shard_map``) sums at
multiplier 1.

Peak-live-buffer estimation (:func:`peak_bytes_of`) runs a last-use
liveness scan per jaxpr level: at each program point the live set is
the jaxpr's inputs plus every already-defined value still referenced
later; the peak adds the deepest concurrently-live sub-jaxpr.  Like the
byte count it is a *scaling* estimator (pre-buffer-assignment, no
aliasing/donation), calibrated by the MemoryFootprint pass's budget
table rather than read as absolute HBM.

Entry points: :func:`cost_of_jaxpr` and :func:`peak_bytes_of`.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Callable, Dict, Optional

from repro.analysis.jaxpr_walk import as_jaxpr

__all__ = ["CostEstimate", "cost_of_jaxpr", "peak_bytes_of",
           "aval_bytes", "register_primitive_cost", "LAYOUT_PRIMS"]


# ---------------------------------------------------------------------------
# Cost container
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class CostEstimate:
    """Additive resource totals for one traced executable."""

    flops: float = 0.0
    hbm_bytes: float = 0.0
    # per collective kind ("all_to_all", "all_gather", "psum", ...):
    coll_payload: Dict[str, float] = dataclasses.field(default_factory=dict)
    coll_wire: Dict[str, float] = dataclasses.field(default_factory=dict)
    coll_count: Dict[str, int] = dataclasses.field(default_factory=dict)
    inexact: bool = False      # a dynamic-trip-count loop was estimated

    def add(self, other: "CostEstimate", mult: float = 1.0) -> None:
        self.flops += other.flops * mult
        self.hbm_bytes += other.hbm_bytes * mult
        for k, v in other.coll_payload.items():
            self.coll_payload[k] = self.coll_payload.get(k, 0.0) + v * mult
        for k, v in other.coll_wire.items():
            self.coll_wire[k] = self.coll_wire.get(k, 0.0) + v * mult
        for k, v in other.coll_count.items():
            self.coll_count[k] = self.coll_count.get(k, 0) + int(v * mult)
        self.inexact = self.inexact or other.inexact

    def total_collective_payload(self) -> float:
        return float(sum(self.coll_payload.values()))

    def total_collective_wire(self) -> float:
        return float(sum(self.coll_wire.values()))


def aval_bytes(aval) -> float:
    """Byte size of one abstract value (0 for non-array avals)."""
    shape = getattr(aval, "shape", None)
    dtype = getattr(aval, "dtype", None)
    if shape is None or dtype is None:
        return 0.0
    return float(math.prod(shape)) * dtype.itemsize


def _out_elems(eqn) -> float:
    return float(sum(math.prod(getattr(v.aval, "shape", ()))
                     for v in eqn.outvars))


def _io_bytes(eqn) -> float:
    return float(sum(aval_bytes(v.aval) for v in eqn.invars) +
                 sum(aval_bytes(v.aval) for v in eqn.outvars))


# ---------------------------------------------------------------------------
# Per-primitive handlers
# ---------------------------------------------------------------------------
#
# A handler takes ``(eqn, axis_sizes)`` and returns a CostEstimate for
# that single equation (sub-jaxpr recursion is the interpreter's job,
# not the handler's).  Unlisted primitives fall back to the default:
# one flop per output element + operand/result bytes — except the pure
# LAYOUT_PRIMS, which cost bytes only.

# Primitives that move/reinterpret data without arithmetic.
LAYOUT_PRIMS = frozenset({
    "reshape", "transpose", "broadcast_in_dim", "convert_element_type",
    "squeeze", "slice", "rev", "concatenate", "pad", "copy",
    "stop_gradient", "iota", "split", "device_put", "sharding_constraint",
    "bitcast_convert_type", "expand_dims",
})

# Zero-cost bookkeeping primitives (no data movement either).
FREE_PRIMS = frozenset({
    "axis_index", "program_id", "num_programs", "create_token",
    "debug_callback", "pure_callback",
})


def _dot_general_cost(eqn, axis_sizes) -> CostEstimate:
    (lhs_c, _rhs_c), _ = eqn.params["dimension_numbers"]
    lhs = eqn.invars[0].aval.shape
    k = math.prod(lhs[d] for d in lhs_c) or 1
    return CostEstimate(flops=2.0 * _out_elems(eqn) * k,
                        hbm_bytes=_io_bytes(eqn))


def _conv_cost(eqn, axis_sizes) -> CostEstimate:
    # out elems × (2 × kernel reduction size); kernel is invars[1] with
    # layout-dependent dims — reduction = all kernel elems / out features.
    rhs = eqn.invars[1].aval.shape
    out_feats = max(1, eqn.outvars[0].aval.shape[1])
    red = math.prod(rhs) / out_feats
    return CostEstimate(flops=2.0 * _out_elems(eqn) * red,
                        hbm_bytes=_io_bytes(eqn))


def _gather_cost(eqn, axis_sizes) -> CostEstimate:
    # Touched bytes: read the gathered slices (≈ result) + the index
    # buffer, write the result.  NOT the whole operand — a cap-bounded
    # plan gather over the KV buffer must not look O(T_kv).
    out_b = sum(aval_bytes(v.aval) for v in eqn.outvars)
    idx_b = aval_bytes(eqn.invars[-1].aval) if len(eqn.invars) > 1 else 0.0
    return CostEstimate(hbm_bytes=2.0 * out_b + idx_b)


def _scatter_cost(eqn, axis_sizes) -> CostEstimate:
    # Read + write the touched window (≈ updates) + indices; the
    # untouched remainder of the operand aliases through.
    upd_b = aval_bytes(eqn.invars[-1].aval)
    idx_b = aval_bytes(eqn.invars[1].aval) if len(eqn.invars) > 2 else 0.0
    flops = float(math.prod(getattr(eqn.invars[-1].aval, "shape", ())))
    return CostEstimate(flops=flops, hbm_bytes=2.0 * upd_b + idx_b)


def _dynamic_slice_cost(eqn, axis_sizes) -> CostEstimate:
    out_b = sum(aval_bytes(v.aval) for v in eqn.outvars)
    return CostEstimate(hbm_bytes=2.0 * out_b)


def _dynamic_update_slice_cost(eqn, axis_sizes) -> CostEstimate:
    upd_b = aval_bytes(eqn.invars[1].aval)
    return CostEstimate(hbm_bytes=2.0 * upd_b)


def _sort_cost(eqn, axis_sizes) -> CostEstimate:
    # comparison-sort proxy: n log2 n per sorted lane
    n = _out_elems(eqn)
    return CostEstimate(flops=n * max(1.0, math.log2(max(n, 2.0))),
                        hbm_bytes=_io_bytes(eqn))


def _axis_size(eqn, axis_sizes, names) -> int:
    if isinstance(names, (str, int)):
        names = (names,)
    p = 1
    for nm in names or ():
        p *= int(axis_sizes.get(nm, 1))
    return max(p, 1)


def _collective_cost(kind: str, payload: float, p: int) -> CostEstimate:
    """payload = HLO-result-comparable bytes; wire = bytes crossing links."""
    wire = {
        "all_to_all": payload * (p - 1) / p,
        "all_gather": payload * (p - 1) / p,     # payload is the result
        "psum": 2.0 * payload * (p - 1) / p,     # reduce-scatter+all-gather
        "psum_scatter": payload * (p - 1),       # payload is the shard
        "reduce_scatter": payload * (p - 1),
        "ppermute": payload,
        "pgather": payload * (p - 1) / p,
    }.get(kind, payload)
    return CostEstimate(coll_payload={kind: payload},
                        coll_wire={kind: wire},
                        coll_count={kind: 1})


def _all_to_all_cost(eqn, axis_sizes) -> CostEstimate:
    p = _axis_size(eqn, axis_sizes, eqn.params.get("axis_name"))
    payload = sum(aval_bytes(v.aval) for v in eqn.outvars)  # == operand
    c = _collective_cost("all_to_all", payload, p)
    c.hbm_bytes = _io_bytes(eqn)
    return c


def _all_gather_cost(eqn, axis_sizes) -> CostEstimate:
    p = int(eqn.params.get("axis_size") or
            _axis_size(eqn, axis_sizes, eqn.params.get("axis_name")))
    payload = sum(aval_bytes(v.aval) for v in eqn.outvars)  # P × operand
    c = _collective_cost("all_gather", payload, max(p, 1))
    c.hbm_bytes = _io_bytes(eqn)
    return c


def _psum_like_cost(kind):
    def handler(eqn, axis_sizes) -> CostEstimate:
        p = _axis_size(eqn, axis_sizes,
                       eqn.params.get("axes") or eqn.params.get("axis_name"))
        payload = sum(aval_bytes(v.aval) for v in eqn.outvars)
        c = _collective_cost(kind, payload, p)
        c.hbm_bytes = _io_bytes(eqn)
        c.flops = _out_elems(eqn)
        return c
    return handler


_HANDLERS: Dict[str, Callable] = {
    "dot_general": _dot_general_cost,
    "conv_general_dilated": _conv_cost,
    "gather": _gather_cost,
    "scatter": _scatter_cost,
    "scatter-add": _scatter_cost,
    "scatter_add": _scatter_cost,
    "scatter_max": _scatter_cost,
    "scatter_min": _scatter_cost,
    "scatter_mul": _scatter_cost,
    "dynamic_slice": _dynamic_slice_cost,
    "dynamic_update_slice": _dynamic_update_slice_cost,
    "sort": _sort_cost,
    "top_k": _sort_cost,
    "approx_top_k": _sort_cost,
    "all_to_all": _all_to_all_cost,
    "all_gather": _all_gather_cost,
    "psum": _psum_like_cost("psum"),
    "psum2": _psum_like_cost("psum"),
    "psum_scatter": _psum_like_cost("psum_scatter"),
    "reduce_scatter": _psum_like_cost("reduce_scatter"),
    "ppermute": _psum_like_cost("ppermute"),
    "pmin": _psum_like_cost("psum"),
    "pmax": _psum_like_cost("psum"),
    "pgather": _psum_like_cost("pgather"),
}


def register_primitive_cost(name: str, handler: Callable) -> None:
    """Install/override the cost handler for primitive ``name``.

    ``handler(eqn, axis_sizes) -> CostEstimate`` accounts ONE equation;
    sub-jaxpr recursion stays with the interpreter.  See the package
    docstring ("adding a primitive cost") for the checklist.
    """
    _HANDLERS[name] = handler


def _default_cost(eqn, axis_sizes) -> CostEstimate:
    name = eqn.primitive.name
    if name in FREE_PRIMS:
        return CostEstimate()
    if name in LAYOUT_PRIMS:
        return CostEstimate(hbm_bytes=_io_bytes(eqn))
    if name.startswith("reduce_"):
        in_elems = float(sum(math.prod(getattr(v.aval, "shape", ()))
                             for v in eqn.invars))
        return CostEstimate(flops=in_elems, hbm_bytes=_io_bytes(eqn))
    # element-wise / everything else: one flop per output element
    return CostEstimate(flops=_out_elems(eqn), hbm_bytes=_io_bytes(eqn))


# ---------------------------------------------------------------------------
# Interpreter
# ---------------------------------------------------------------------------

# Higher-order primitives with their own recursion rule; anything else
# carrying a sub-jaxpr in its params (pjit, custom_jvp_call, remat, ...)
# sums the body at multiplier 1 on top of a zero own-cost.
def _grid_size(eqn) -> float:
    gm = eqn.params.get("grid_mapping")
    grid = getattr(gm, "grid", None) or eqn.params.get("grid") or ()
    return float(math.prod(int(g) for g in grid)) or 1.0


def _declared_cost(ce) -> CostEstimate:
    """A kernel's own ``pl.CostEstimate``: its FLOPs with one per
    transcendental, as :func:`_default_cost` counts an ``exp``, and the
    HBM bytes it moves.  The body's VMEM loads and stores are not HBM
    traffic, so the grid-times-body walk would overcount them."""
    return CostEstimate(flops=float(ce.flops + ce.transcendentals),
                        hbm_bytes=float(ce.bytes_accessed))


def _sub_jaxprs_of(eqn):
    from repro.analysis.jaxpr_walk import _sub_jaxprs
    return list(_sub_jaxprs(eqn.params))


def cost_of_jaxpr(jaxpr, *, axis_sizes: Optional[dict] = None
                  ) -> CostEstimate:
    """Symbolic resource totals for a traced jaxpr (ClosedJaxpr ok).

    ``axis_sizes`` maps mesh axis names to sizes for collectives traced
    OUTSIDE a ``shard_map`` (inside one, the mesh param wins).
    """
    return _cost(as_jaxpr(jaxpr), dict(axis_sizes or {}))


def _cost(jaxpr, axis_sizes: dict) -> CostEstimate:
    total = CostEstimate()
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        subs = _sub_jaxprs_of(eqn)
        if name == "scan" and subs:
            body = _cost(subs[0], axis_sizes)
            total.add(body, float(eqn.params.get("length") or 1))
        elif name in ("while", "while_loop") and subs:
            for sub in subs:                      # cond + body, one trip
                total.add(_cost(sub, axis_sizes))
            total.inexact = True
        elif name == "cond" and subs:
            branches = [_cost(sub, axis_sizes) for sub in subs]
            worst = CostEstimate()
            for b in branches:
                worst.flops = max(worst.flops, b.flops)
                worst.hbm_bytes = max(worst.hbm_bytes, b.hbm_bytes)
                for k, v in b.coll_payload.items():
                    worst.coll_payload[k] = max(
                        worst.coll_payload.get(k, 0.0), v)
                for k, v in b.coll_wire.items():
                    worst.coll_wire[k] = max(worst.coll_wire.get(k, 0.0), v)
                for k, v in b.coll_count.items():
                    worst.coll_count[k] = max(worst.coll_count.get(k, 0), v)
                worst.inexact = worst.inexact or b.inexact
            total.add(worst)
        elif name == "shard_map" and subs:
            inner_axes = dict(axis_sizes)
            mesh = eqn.params.get("mesh")
            if mesh is not None:
                inner_axes.update({k: int(v)
                                   for k, v in dict(mesh.shape).items()})
            for sub in subs:
                total.add(_cost(sub, inner_axes))
        elif name == "pallas_call" and eqn.params.get("cost_estimate"):
            total.add(_declared_cost(eqn.params["cost_estimate"]))
        elif name == "pallas_call" and subs:
            mult = _grid_size(eqn)
            for sub in subs:
                total.add(_cost(sub, axis_sizes), mult)
        elif subs:
            # pjit / custom_jvp_call / remat / closed_call / ...
            for sub in subs:
                total.add(_cost(sub, axis_sizes))
        else:
            handler = _HANDLERS.get(name, _default_cost)
            total.add(handler(eqn, axis_sizes))
    return total


# ---------------------------------------------------------------------------
# Peak-live-buffer estimator
# ---------------------------------------------------------------------------

def peak_bytes_of(jaxpr) -> float:
    """Peak concurrently-live bytes via a per-level last-use scan.

    At equation ``i`` the live set is the jaxpr's inputs/consts plus
    every defined value whose last use is at or after ``i``, plus the
    equation's own outputs; a sub-jaxpr contributes its own peak on top
    of the point it runs at.  Scale estimator, not buffer assignment:
    no donation, aliasing, or rematerialisation modelling.
    """
    return _peak(as_jaxpr(jaxpr))


def _var_key(v):
    return id(v)


def _peak(jaxpr) -> float:
    eqns = jaxpr.eqns
    base = {}
    for v in list(jaxpr.invars) + list(jaxpr.constvars):
        base[_var_key(v)] = aval_bytes(v.aval)

    last_use = {}
    n = len(eqns)
    for i, eqn in enumerate(eqns):
        for v in eqn.invars:
            if hasattr(v, "aval") and not _is_literal(v):
                last_use[_var_key(v)] = i
    for v in jaxpr.outvars:
        if hasattr(v, "aval") and not _is_literal(v):
            last_use[_var_key(v)] = n

    live = dict(base)            # var key -> bytes, currently live
    peak = float(sum(live.values()))
    for i, eqn in enumerate(eqns):
        for v in eqn.outvars:
            live[_var_key(v)] = aval_bytes(v.aval)
        here = float(sum(live.values()))
        sub_peak = 0.0
        subs = _sub_jaxprs_of(eqn)
        if subs:
            sub_peak = max(_peak(sub) for sub in subs)
            # the sub-jaxpr's inputs/outputs are already in ``here`` as
            # this eqn's operands/results; only the EXTRA interior
            # footprint stacks on top.
            boundary = sum(aval_bytes(v.aval) for v in eqn.invars
                           if hasattr(v, "aval")) + \
                sum(aval_bytes(v.aval) for v in eqn.outvars)
            sub_peak = max(0.0, sub_peak - boundary)
        peak = max(peak, here + sub_peak)
        # retire values whose last use was this equation
        for v in eqn.invars:
            if not hasattr(v, "aval") or _is_literal(v):
                continue
            k = _var_key(v)
            if last_use.get(k, n) <= i and k in live and k not in base:
                del live[k]
    return peak


def _is_literal(v) -> bool:
    return type(v).__name__ == "Literal"

import os
os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=512")
# ^ MUST precede every other import (jax locks the device count on first
#   init).  The 512 placeholder host devices exist ONLY for the dry-run.
#   ``setdefault`` so CI can pin a smaller forced-device count (the
#   8-device sharded-parity job reuses ``--sharded-gate`` on its mesh).

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell and
record memory/cost/collective analyses for EXPERIMENTS.md §Dry-run/§Roofline.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch gemma3-1b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod]
Artifacts: artifacts/dryrun/<arch>__<shape>__<mesh>.json
"""

import argparse
import json
import re
import time
import traceback
from pathlib import Path

import jax

from repro.configs.base import SHAPES
from repro.configs.registry import ARCH_IDS, arch_shapes, get_config
from repro.launch.mesh import make_production_mesh, rules_for
from repro.launch import steps as ST

_DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s64": 8, "u64": 8,
                "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
                "pred": 1, "f8e4m3": 1, "f8e5m2": 1}

# HLO text: ``%all-reduce.705 = f32[256,4096]{1,0} all-reduce(%x), ...`` —
# operands are bare names; we account the RESULT shape as bytes moved
# (all-gather result = bytes received per device; all-reduce ≈ tensor size;
# reduce-scatter result = shard received; a2a tuple = total moved).
_COLL_RE = re.compile(
    r"=\s+(\([^)]*\)|[a-z0-9_\[\]{},]+)\s+"
    r"(ragged-all-to-all|all-gather|all-reduce|reduce-scatter|all-to-all|"
    r"collective-permute|collective-broadcast)"
    r"(?:-start)?\(")
# ``ragged-all-to-all`` must precede ``all-to-all`` in the alternation and
# both must be present: the plan-sharded dispatch exchange lowers to one of
# these, and a gate reading 0 bytes because the op name was missing from
# this list would pass vacuously (see ``--sharded-gate``).
_SHAPE_RE = re.compile(r"(f64|f32|bf16|f16|s64|u64|s32|u32|s16|u16|s8|u8|pred|"
                       r"f8e4m3\w*|f8e5m2\w*)\[([0-9,]*)\]")


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    for k, v in _DTYPE_BYTES.items():
        if dtype.startswith(k):
            return n * v
    return n * 4


def collective_bytes(hlo_text: str) -> dict:
    """Sum result bytes per collective kind (async `-done` ops excluded)."""
    out: dict = {}
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if not m:
            continue
        kind = m.group(2)
        total = sum(_shape_bytes(d, s) for d, s in _SHAPE_RE.findall(m.group(1)))
        out[kind] = out.get(kind, 0) + total
        out.setdefault(kind + "_count", 0)
        out[kind + "_count"] += 1
    return out


def sharded_dispatch_report(out_dir: Path, *, mesh_sp: int = 8,
                            density: float = 0.25,
                            pair_slack: float = 1.5) -> dict:
    """Account the plan-sharded dispatch's collective bytes statically.

    Builds a small engine cell at ``cap_kv_frac = density`` and traces the
    mesh-sharded attention (``distributed/plan_shard.mesh_attention``) plus
    a dense baseline that all-gathers the full K/V over the same mesh.  The
    byte totals come from the STATIC cost model
    (:func:`repro.analysis.cost_model.cost_of_jaxpr` over the jaxprs — the
    same interpreter the ``cost-collective-bytes`` analyzer pass certifies
    against the ``pair_cap`` formula), so the numbers are exact by
    construction and independent of HLO lowering details.  The compiled
    HLO is still parsed via :func:`collective_bytes`, but only as a
    CROSS-CHECK recorded in the report: ``--sharded-gate`` asserts the HLO
    parse sees nonzero all-to-all bytes agreeing with the static payload,
    so a stale op regex (the PR-7 whack-a-mole) or a lowering that stops
    matching the model both fail loudly instead of gating vacuously.

    The plan-aware exchange ships only ``mesh_sp · pair_cap`` blocks per
    shard (vs ``T_kv`` for the dense all-gather), so at 25% density and
    default slack the ratio lands at
    ``⌈slack · cap_kv / P⌉ · P / T_kv ≈ 0.375`` — the ``--sharded-gate``
    CI flag asserts it stays below 0.5.
    """
    import jax.numpy as jnp

    from repro.core import engine as E
    from repro.core.backend import get_backend
    from repro.core.engine import (AttnParams, EngineConfig, init_layer_state,
                                   update_layer)
    from repro.core.masks import MaskConfig
    from repro.distributed.plan_shard import (dense_exchange_blocks,
                                              exchange_blocks, shard_geometry)
    from repro.launch.mesh import make_engine_mesh

    b, heads, n, dm, dh = 1, 2, 1024, 32, 16
    m = MaskConfig(tau_q=0.5, tau_kv=0.15, interval=4, order=1, degrade=0.3,
                   block_q=16, block_kv=16, pool=16, warmup_steps=2)
    cfg = EngineConfig(mask=m, backend="xla", cap_kv_frac=density,
                       mesh_dp=1, mesh_sp=mesh_sp, mesh_pair_slack=pair_slack)
    key = jax.random.PRNGKey(0)
    ks = jax.random.split(key, 5)
    params = AttnParams(
        wq=jax.random.normal(ks[0], (dm, heads * dh)) * 0.05,
        wk=jax.random.normal(ks[1], (dm, heads * dh)) * 0.05,
        wv=jax.random.normal(ks[2], (dm, heads * dh)) * 0.05,
        wo=jax.random.normal(ks[3], (heads * dh, dm)) * 0.05,
        q_scale=jnp.ones((dh,)), k_scale=jnp.ones((dh,)))
    x = jax.random.normal(ks[4], (b, n, dm), jnp.float32)
    st0 = init_layer_state(b, heads, n, dm, dh, cfg)
    _, st = update_layer(params, x, st0, cfg, heads=heads)
    plan = st.plan.widen()
    spec = cfg.caps(n)
    q, k = E._qk(params, x, heads, None)
    v = E._project_heads(x, params.wv, heads)
    o_reuse = jnp.zeros((b, heads, n, dh), q.dtype)

    from repro.analysis.cost_model import cost_of_jaxpr

    backend = get_backend(cfg)                       # MeshBackend(xla)

    def attn(q_, k_, v_, o_):
        return backend.attention(q_, k_, v_, o_, plan, spec)

    # Source of truth: the static cost model over the traced jaxpr.
    scost = cost_of_jaxpr(jax.make_jaxpr(attn)(q, k, v, o_reuse))
    plan_bytes = scost.coll_payload.get("all_to_all", 0.0)
    extra_kinds = {k_: v_ for k_, v_ in scost.coll_payload.items()
                   if k_ != "all_to_all" and v_}

    mesh = make_engine_mesh(1, mesh_sp)
    from jax.experimental.shard_map import shard_map
    from jax.sharding import PartitionSpec as PS

    def dense(k_, v_):
        kg = jax.lax.all_gather(k_, "seq", axis=2, tiled=True)
        vg = jax.lax.all_gather(v_, "seq", axis=2, tiled=True)
        return kg, vg

    dfn = shard_map(dense, mesh=mesh,
                    in_specs=(PS(None, None, "seq", None),) * 2,
                    out_specs=(PS(None, None, None, None),) * 2,
                    check_rep=False)
    dcost = cost_of_jaxpr(jax.make_jaxpr(dfn)(k, v))
    dense_bytes = dcost.coll_payload.get("all_gather", 0.0)

    t_q = m.n_blocks(n) * (m.pool // m.block_q)
    t_kv = m.n_blocks(n) * (m.pool // m.block_kv)
    geom = shard_geometry(spec, t_q, t_kv, mesh_sp, pair_slack)
    # Closed-form expectation: one exchange per K and V of
    # (b, heads, P·pair_cap·block_kv, dh) blocks.
    formula_bytes = 2.0 * (b * heads * mesh_sp * geom.pair_cap
                           * m.block_kv * dh) * q.dtype.itemsize

    # Cross-check only: parse the compiled HLO with the legacy regex.
    coll = collective_bytes(
        jax.jit(attn).lower(q, k, v, o_reuse).compile().as_text())
    hlo_plan = sum(v_ for k_, v_ in coll.items()
                   if "all-to-all" in k_ and not k_.endswith("_count"))
    dcoll = collective_bytes(jax.jit(dfn).lower(k, v).compile().as_text())
    hlo_dense = sum(v_ for k_, v_ in dcoll.items()
                    if "all-gather" in k_ and not k_.endswith("_count"))

    rec = {
        "mesh_sp": mesh_sp, "density": density, "pair_slack": pair_slack,
        "plan_collective_bytes": plan_bytes,
        "dense_collective_bytes": dense_bytes,
        "ratio": plan_bytes / dense_bytes if dense_bytes else float("inf"),
        "formula_bytes": formula_bytes,
        "static_extra_collectives": extra_kinds,
        "hlo_plan_collective_bytes": hlo_plan,
        "hlo_dense_collective_bytes": hlo_dense,
        "hlo_crosscheck_rel_err": (abs(hlo_plan - plan_bytes)
                                   / plan_bytes if plan_bytes else
                                   float("inf")),
        "exchange_blocks_per_shard": exchange_blocks(geom),
        "dense_exchange_blocks": dense_exchange_blocks(t_kv),
        "sharded_hlo_collectives": coll,
        "dense_hlo_collectives": dcoll,
    }
    out_dir.mkdir(parents=True, exist_ok=True)
    path = out_dir / f"sharded_dispatch__sp{mesh_sp}__d{density}.json"
    path.write_text(json.dumps(rec, indent=1, default=str))
    print(f"[dryrun] sharded dispatch: plan={plan_bytes}B "
          f"dense={dense_bytes}B ratio={rec['ratio']:.3f} -> {path}")
    return rec


def run_cell(arch: str, shape_name: str, multi_pod: bool, out_dir: Path,
             *, mode_override: str | None = None, unroll: bool = False) -> dict:
    import dataclasses
    cfg = get_config(arch)
    if unroll:
        # Exact roofline accounting: lower the layer loop explicitly so
        # cost_analysis sees every layer (see models.layers.maybe_scan).
        cfg = dataclasses.replace(cfg, scan_layers=False)
    mesh = make_production_mesh(multi_pod=multi_pod)
    shapes = {s.name: s for s in arch_shapes(cfg)}
    shape = shapes[shape_name]
    rules = rules_for(cfg, shape, multi_pod=multi_pod)

    if cfg.family == "dit":
        mode = mode_override or "dispatch"
        fn, in_shapes, in_sh, out_sh = ST.build_dit_step(cfg, shape, mesh, rules,
                                                         mode=mode)
        entry = f"denoise_{mode}"
    elif shape.kind == "train":
        fn, in_shapes, in_sh, out_sh = ST.build_train_step(cfg, shape, mesh, rules)
        entry = "train_step"
    elif shape.kind == "prefill":
        fn, in_shapes, in_sh, out_sh = ST.build_prefill_step(cfg, shape, mesh, rules)
        entry = "prefill"
    else:
        fn, in_shapes, in_sh, out_sh = ST.build_decode_step(cfg, shape, mesh, rules)
        entry = "decode_step"

    t0 = time.time()
    with mesh:
        lowered = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh).lower(*in_shapes)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    mem = compiled.memory_analysis()
    mem_rec = {}
    for attr in ("argument_size_in_bytes", "output_size_in_bytes",
                 "temp_size_in_bytes", "alias_size_in_bytes",
                 "generated_code_size_in_bytes"):
        mem_rec[attr] = getattr(mem, attr, None)
    cost = dict(compiled.cost_analysis() or {})
    hlo = compiled.as_text()
    coll = collective_bytes(hlo)

    rec = {
        "arch": arch, "shape": shape_name, "entry": entry,
        "mesh": "pod2x16x16" if multi_pod else "pod16x16",
        "n_devices": mesh.devices.size,
        "seq_len": shape.seq_len, "global_batch": shape.global_batch,
        "flops_per_device": cost.get("flops"),
        "bytes_per_device": cost.get("bytes accessed"),
        "cost_analysis": {k: v for k, v in cost.items()
                          if isinstance(v, (int, float)) and
                          ("flops" in k or "bytes" in k or "utilization" in k)},
        "memory_analysis": mem_rec,
        "collective_bytes": coll,
        "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
        "n_params": get_config(arch).n_params(),
        "n_active_params": get_config(arch).n_active_params(),
    }
    out_dir.mkdir(parents=True, exist_ok=True)
    suffix = f"__{mode_override}" if mode_override else ""
    if unroll:
        rec["unrolled"] = True
        suffix += "__unroll"
    path = out_dir / f"{arch}__{shape_name}__{rec['mesh']}{suffix}.json"
    path.write_text(json.dumps(rec, indent=1, default=str))
    print(f"[dryrun] OK {arch} {shape_name} {rec['mesh']}{suffix} "
          f"flops/dev={rec['flops_per_device']} compile={t_compile:.1f}s -> {path}")
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--mode", default=None, help="dit: update|dispatch")
    ap.add_argument("--unroll", action="store_true",
                    help="unroll layer loops for exact cost analysis")
    ap.add_argument("--sharded-gate", action="store_true",
                    help="lower the plan-sharded dispatch at 25%% density "
                         "and assert its collective bytes < 0.5x the dense "
                         "KV all-gather over the same mesh")
    ap.add_argument("--mesh-sp", type=int, default=8,
                    help="seq-shard count for --sharded-gate")
    ap.add_argument("--out", default="artifacts/dryrun")
    args = ap.parse_args()
    out_dir = Path(args.out)

    if args.sharded_gate:
        rec = sharded_dispatch_report(out_dir, mesh_sp=args.mesh_sp)
        if not rec["plan_collective_bytes"]:
            raise SystemExit("[dryrun] sharded gate: static model sees 0 "
                             "all_to_all bytes in the sharded dispatch — "
                             "the exchange vanished from the trace")
        if rec["plan_collective_bytes"] != rec["formula_bytes"]:
            raise SystemExit(
                f"[dryrun] sharded gate FAIL: static a2a payload "
                f"{rec['plan_collective_bytes']:.0f}B != pair_cap formula "
                f"{rec['formula_bytes']:.0f}B")
        if rec["static_extra_collectives"]:
            raise SystemExit(
                f"[dryrun] sharded gate FAIL: unexpected collectives "
                f"{rec['static_extra_collectives']} in the sharded dispatch")
        if rec["ratio"] >= 0.5:
            raise SystemExit(f"[dryrun] sharded gate FAIL: plan-aware "
                             f"exchange at {rec['ratio']:.3f}x dense (>= 0.5)")
        # Cross-check: the legacy HLO-text parse must still see the same
        # exchange, or the regex went stale / the lowering diverged.
        if not rec["hlo_plan_collective_bytes"]:
            raise SystemExit("[dryrun] sharded gate: 0 collective bytes read "
                             "from the sharded HLO — op regex is stale")
        if rec["hlo_crosscheck_rel_err"] > 0.25:
            raise SystemExit(
                f"[dryrun] sharded gate FAIL: HLO parse "
                f"({rec['hlo_plan_collective_bytes']:.0f}B) disagrees with "
                f"the static model ({rec['plan_collective_bytes']:.0f}B) by "
                f"{rec['hlo_crosscheck_rel_err']:.1%} (> 25%)")
        print(f"[dryrun] sharded gate OK: {rec['ratio']:.3f}x dense "
              f"(static == pair_cap formula; HLO cross-check "
              f"{rec['hlo_crosscheck_rel_err']:.1%})")
        return

    cells = []
    if args.all:
        for arch in ARCH_IDS:
            for sh in arch_shapes(get_config(arch)):
                cells.append((arch, sh.name))
    else:
        assert args.arch and args.shape
        cells = [(args.arch, args.shape)]

    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    failures = []
    for arch, sh in cells:
        for mp in meshes:
            try:
                run_cell(arch, sh, mp, out_dir, mode_override=args.mode,
                         unroll=args.unroll)
            except Exception as e:  # noqa: BLE001 — record and continue
                failures.append((arch, sh, mp, repr(e)))
                print(f"[dryrun] FAIL {arch} {sh} multi_pod={mp}: {e}")
                traceback.print_exc()
    if failures:
        print(f"\n{len(failures)} FAILURES:")
        for f in failures:
            print("  ", f)
        raise SystemExit(1)
    print(f"\nAll {len(cells) * len(meshes)} dry-run cells compiled OK.")


if __name__ == "__main__":
    main()

"""Readings that the limits of ``correct`` are set from, on the chip.

    python -m chipbench.control --workload <cell> --seeds 11 12 13 \\
        [--control] [--engine cap_q_frac=0.75]

For each seed, in one process: one request served through the timed path
(``chipbench.run.Server``) at the cell's own sizes, and the plain
reference for the same inputs.  It prints the numbers that decide
``correct`` for the program against the reference (the lower readings)
and, with ``--control``, for the reference computed with float8 (e4m3)
matrix operands against the same reference (the upper readings: the
control, one precision below the bfloat16 the configuration states).
``--engine key=value`` overrides an engine setting of the configuration,
to show a fault of the program next to a witness.  Each reading is one
JSON line on stdout.
"""

from __future__ import annotations

import argparse
import json
import time

from chipbench import bench as B
from chipbench import run as R


def readings(cell: dict, seeds, control: bool, engine_overrides=None,
             index: int = 1, devices=None):
    """Yield one dict of readings per seed, the reference computed on
    ``devices`` (default: one chip)."""
    import jax.numpy as jnp

    from chipbench import reference

    for seed in seeds:
        t = time.perf_counter()
        server = R.Server(cell, seed, engine_overrides)
        res = server.serve(server.request(index))
        served = time.perf_counter() - t
        x0, ref = server.reference(index, devices=devices)
        row = {"seed": seed, "served_s": served,
               "reference_s": time.perf_counter() - t - served,
               "program": reference.compare(res["out"], ref, x0)}
        dens = [st["density"] for st in res["trace"]
                if st["kind"] == "dispatch"]
        row["dispatch_density"] = sum(dens) / max(len(dens), 1)
        if control:
            _, low = server.reference(index, compute=jnp.float8_e4m3fn,
                                      devices=devices)
            row["control"] = reference.compare(low, ref, x0)
        del server
        yield row


def _override(text: str):
    key, _, value = text.partition("=")
    return key, json.loads(value)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--engine", type=_override, action="append", default=[])
    args = ap.parse_args(argv)
    bench = B.load_benchmark()
    cell = B.find_cell(bench, args.workload)
    devices = R.require_tpu(cell["chips"])
    R.import_program()
    from repro.launch.serve import enable_compile_cache
    enable_compile_cache()
    for row in readings(cell, args.seeds, args.control, dict(args.engine),
                        devices=devices):
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())

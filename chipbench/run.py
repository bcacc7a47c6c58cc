#!/usr/bin/env python3
"""The chip benchmark: one run of one cell of `BENCHMARK.json`.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout, on a machine whose JAX sees a TPU.  The
last line of standard output is one JSON object: `correct`, `attempted`,
`failed`, `metrics` (the cell's end-to-end metrics, or with `--trace 1`
its per-layer ones), `device`, with `--trace 1` a `breakdown`, and last
`checks`, every number compared with its limit (also the last lines of
standard error).  Earlier lines give set-up split into its parts and
the number of compiles inside the window; where that is not 0 the run
prints no result and exits non-zero.

`--control keys32` runs the lower-precision control: the same run with
32-bit node keys (64-bit mode off), which the comparison must fail.
`--allow-cpu` rehearses a run where JAX finds no TPU; it then reports
nothing and exits non-zero.  `--root` points at another manifest.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse
import json
import os
import sys
from pathlib import Path

CHECKOUT = Path(__file__).resolve().parents[1]
NO_RESULT = 3


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", choices=("keys32",), default=None)
    ap.add_argument("--allow-cpu", action="store_true")
    ap.add_argument("--root", type=Path, default=CHECKOUT)
    args = ap.parse_args(argv)

    # the compile cache lives in the checkout, at a fixed path, and the
    # program's `enable_compile_cache` takes it from this variable
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CHECKOUT / ".jax_cache")
    sys.path[:0] = [str(CHECKOUT / "src"), str(CHECKOUT)]
    import jax

    # the configurations state 64-bit node identity
    jax.config.update("jax_enable_x64", args.control != "keys32")
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    # no size limit, so no eviction: an evicting cache reads an access-time
    # file beside every entry, and one entry copied in without it makes
    # every later write fail, so that each run compiles everything again
    jax.config.update("jax_compilation_cache_max_size", -1)

    from chipbench import harness
    from chipbench.compile_log import CompileLog
    from chipbench.manifest import load_cell
    from repro.launch.compile_cache import enable_compile_cache

    cell = load_cell(args.root, args.workload)
    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    on_tpu = device["platform"] == "tpu"
    harness.log(f"device: {device}; compile cache: {enable_compile_cache()}")
    if not on_tpu and not args.allow_cpu:
        harness.log("chipbench: JAX found no TPU; no result")
        return NO_RESULT
    if device["count"] < cell.chips:
        harness.log(f"chipbench: {args.workload} needs {cell.chips} chips, "
                    f"JAX found {device['count']}; no result")
        return NO_RESULT

    compiles = CompileLog()
    out, in_window = harness.run(args.root, args.workload, args.seed,
                                 args.seconds, bool(args.trace), T_START,
                                 compiles, device, require_tpu=on_tpu)
    print(f"correct: {out['correct']}", flush=True)
    for name, c in out["checks"].items():
        harness.log(f"check {name}: {c['value']} (limit {c['limit']})")
    if in_window:
        harness.log(f"chipbench: {in_window} programs compiled or loaded "
                    "inside the window, so it measured compiling; no result")
        return NO_RESULT
    if not on_tpu:
        harness.log("chipbench: a rehearsal on the CPU reports no result")
        return NO_RESULT
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

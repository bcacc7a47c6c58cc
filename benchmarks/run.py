"""Benchmark harness: one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (us_per_call where timing
is meaningful; structural benches print the primary metric instead).

  PYTHONPATH=src python -m benchmarks.run            # everything
  PYTHONPATH=src python -m benchmarks.run --only compression
  PYTHONPATH=src python -m benchmarks.run --only store_ingest,snapshot_build

With ``--json`` the full results go to the given file AND the ingest
perf trajectory (per-commit wall time, probe rounds, dropped inserts,
snapshot delta-apply vs full-rebuild timings, per-scenario workload
rows) is merge-appended as a new run entry into ``BENCH_ingest.json``
next to it — earlier runs are preserved, so the file accumulates the
perf trajectory PR over PR instead of only holding the latest run.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

# bench names whose results belong in the BENCH_ingest.json trajectory
TRAJECTORY_BENCHES = ("ingest_trajectory", "store_ingest", "snapshot_build",
                      "workload_scenarios", "compress_dictionary",
                      "telemetry_overhead", "resilience_chaos",
                      "monitor_overhead", "lineage_overhead",
                      "lineage_freshness")

BENCHES = [
    # (name, module, function, paper ref)
    ("uncontrolled_meltdown", "benchmarks.bench_ingestion", "bench_uncontrolled", "Figs 1-3,7"),
    ("controlled_bounded_cpu", "benchmarks.bench_ingestion", "bench_controlled", "Fig 12"),
    ("graph_compression", "benchmarks.bench_ingestion", "bench_compression", "Fig 13"),
    ("prediction_models", "benchmarks.bench_ingestion", "bench_prediction", "Table I, Fig 11"),
    ("ingestor_node_health", "benchmarks.bench_ingestion", "bench_ingestor_node", "Fig 14"),
    ("ingest_trajectory", "benchmarks.bench_ingestion", "bench_ingest_trajectory", "Alg 3 hot path (BENCH_ingest.json)"),
    ("dedup_throughput", "benchmarks.bench_kernels", "bench_dedup_throughput", "Alg 1 hot path"),
    ("store_ingest", "benchmarks.bench_kernels", "bench_store_ingest", "Alg 3 hot path"),
    ("attention_paths", "benchmarks.bench_kernels", "bench_attention_paths", "LM substrate"),
    ("ssd_chunked_speedup", "benchmarks.bench_kernels", "bench_ssd_vs_naive", "LM substrate"),
    ("workload_scenarios", "benchmarks.bench_workloads", "bench_scenarios", "scenario family (Alg 2 under adversarial streams)"),
    ("compress_dictionary", "benchmarks.bench_compress", "bench_compress_dictionary", "GraphZip dictionary compression (Fig 13 + refs)"),
    ("telemetry_overhead", "benchmarks.bench_telemetry", "bench_telemetry_overhead", "observability cost (spans on vs off, steady_state)"),
    ("monitor_overhead", "benchmarks.bench_monitor", "bench_monitor_overhead", "online health-monitor cost + controller score (repro.monitor)"),
    ("lineage_overhead", "benchmarks.bench_lineage", "bench_lineage_overhead", "watermark/provenance tracking cost (repro.lineage)"),
    ("lineage_freshness", "benchmarks.bench_lineage", "bench_lineage_freshness", "freshness SLIs per scenario (repro.lineage)"),
    ("resilience_chaos", "benchmarks.bench_resilience", "bench_resilience", "checkpoint/resume + backoff retry (repro.resilience)"),
    ("sketch_update", "benchmarks.bench_query", "bench_sketch_update", "GSS/TCM sketch (Gou 2018)"),
    ("snapshot_build", "benchmarks.bench_query", "bench_snapshot_build", "store->CSR compaction"),
    ("query_latency", "benchmarks.bench_query", "bench_query_latency", "streaming graph queries (Pacaci 2021)"),
]


def merge_bench_ingest(path: str, traj: dict) -> int:
    """Append `traj` as a new run entry in the BENCH_ingest.json perf
    trajectory, preserving earlier runs (a legacy single-run file is
    wrapped as run 0).  Returns the new run count."""
    runs = []
    if os.path.exists(path):
        try:
            with open(path) as f:
                prev = json.load(f)
            if isinstance(prev, dict) and isinstance(prev.get("runs"), list):
                runs = prev["runs"]
            elif isinstance(prev, dict) and prev:
                runs = [{"run": 0, "note": "legacy single-run format",
                         "benches": prev}]
        except (OSError, ValueError) as e:
            # unreadable trajectory: keep the evidence (the file is the
            # repo's perf history — never silently discard it), start a
            # fresh trajectory, and say so loudly
            n = 0
            while os.path.exists(f"{path}.bak-{n}"):
                n += 1
            bak = f"{path}.bak-{n}"
            os.replace(path, bak)
            print(f"WARNING: {path} is corrupt ({e}); renamed it to "
                  f"{bak} and starting a fresh trajectory", file=sys.stderr)
    runs.append({
        "run": len(runs),
        "recorded_at": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "benches": traj,
    })
    with open(path, "w") as f:
        json.dump({"runs": runs}, f, indent=2, default=str)
    return len(runs)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated substrings of bench names")
    ap.add_argument("--json", default=None, help="also dump results to file")
    args = ap.parse_args()
    only = [s for s in (args.only or "").split(",") if s]

    import importlib

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()

    all_results = {}
    print("name,us_per_call,derived")
    n_failed = 0
    for name, mod, fn, ref in BENCHES:
        if only and not any(s in name for s in only):
            continue
        t0 = time.perf_counter()
        try:
            rows, derived = getattr(importlib.import_module(mod), fn)()
        except Exception as e:  # one broken bench must not abort the suite
            n_failed += 1
            print(f"{name},,{json.dumps({'error': repr(e)})}")
            all_results[name] = {"error": repr(e), "paper_ref": ref}
            continue
        us = (time.perf_counter() - t0) * 1e6
        us_field = ""
        if rows and "us_per_call" in rows[0]:
            us_field = f"{rows[0]['us_per_call']}"
        elif rows and "us_per_commit" in rows[0]:
            us_field = f"{rows[0]['us_per_commit']}"
        # long per-commit series stay out of stdout (BENCH_ingest.json)
        show = {k: v for k, v in derived.items() if not k.endswith("trajectory")}
        print(f"{name},{us_field},{json.dumps(show, default=str)}")
        for r in rows:
            print(f"  {name}.row,,{json.dumps(r, default=str)}")
        all_results[name] = {"rows": rows, "derived": derived, "paper_ref": ref,
                             "bench_wall_us": us}
    # roofline table from dry-run artifacts, if present
    try:
        from benchmarks.roofline import load_cells, table

        cells = load_cells()
        if cells:
            print("\n== roofline (single-pod) ==")
            print(table(cells, "16x16"))
    except Exception as e:  # dry-run results absent: fine
        print(f"(roofline table skipped: {e})")
    if args.json:
        with open(args.json, "w") as f:
            json.dump(all_results, f, indent=2, default=str)
        print(f"(wrote {len(all_results)} bench results to {args.json})")
        # ingest perf-trajectory file: the hot-path regression record
        traj = {
            name: all_results[name]
            for name in TRAJECTORY_BENCHES
            if name in all_results
        }
        if traj:
            path = os.path.join(os.path.dirname(os.path.abspath(args.json)),
                                "BENCH_ingest.json")
            n = merge_bench_ingest(path, traj)
            print(f"(appended ingest perf trajectory to {path}: "
                  f"run {n - 1}, {n} total)")
    if n_failed:
        print(f"({n_failed} bench(es) failed; see error rows above)")
        sys.exit(1)


if __name__ == "__main__":
    main()

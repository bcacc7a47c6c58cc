"""Query-engine entrypoint: ingest a stream, then query the graph.

  PYTHONPATH=src python -m repro.launch.query                  # ingest->query
  PYTHONPATH=src python -m repro.launch.query --mode live      # query-while-ingesting
  PYTHONPATH=src python -m repro.launch.query --dryrun         # CI smoke

Ingests a simulated burst through the composable pipeline with the
ingestion-time sketch enabled (`SketchStage` after the filter, plus a
commit-consistent `QuerySink` around the store sink), then compacts
the store into a CSR snapshot and runs the exact engine ops — degree
distribution, top-k heavy nodes, k-hop expansion, triangle count —
printing sketch estimates next to exact answers.  In `--mode live`
the sketch's heavy-hitter answers stream to stdout *during* ingestion
via the MetricsHub "sketch" events.

x64 is enabled for exact 64-bit node identity (as in launch.ingest).
"""
import jax

jax.config.update("jax_enable_x64", True)

import argparse
import time

import numpy as np

from repro.api import PipelineBuilder, GraphStoreSink
from repro.configs.paper_ingest import IngestConfig
from repro.ingest.sources import BurstyTweetSource
from repro.launch.compile_cache import enable_compile_cache


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--ticks", type=int, default=120)
    ap.add_argument("--rate", type=float, default=60.0)
    ap.add_argument("--burst", type=float, default=5.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--mode", choices=["snapshot", "live"], default="snapshot",
                    help="snapshot: ingest then query; live: print sketch "
                         "answers during ingestion, then query")
    ap.add_argument("--depth", type=int, default=4, help="sketch depth D")
    ap.add_argument("--width", type=int, default=512, help="sketch width W")
    ap.add_argument("--node-cap", type=int, default=1 << 12)
    ap.add_argument("--edge-cap", type=int, default=1 << 14)
    ap.add_argument("--topk", type=int, default=10)
    ap.add_argument("--hops", type=int, default=2)
    ap.add_argument("--query-every", type=int, default=20,
                    help="live mode: emit sketch answers every N commits")
    ap.add_argument("--dryrun", action="store_true",
                    help="tiny end-to-end run (CI smoke)")
    args = ap.parse_args(argv)
    enable_compile_cache()
    if args.dryrun:
        args.ticks = min(args.ticks, 25)
        args.node_cap, args.edge_cap = 1 << 11, 1 << 12
        args.width = 256

    from repro.query import (
        SketchStage, degree_distribution, edge_lookup,
        k_hop, top_k_degree, triangle_count,
    )

    cfg = IngestConfig(mean_rate=args.rate, burst_multiplier=args.burst,
                       store_nodes=args.node_cap, store_edges=args.edge_cap)
    src = BurstyTweetSource(seed=args.seed, mean_rate=args.rate,
                            burst_multiplier=args.burst)
    sketch_stage = SketchStage(depth=args.depth, width=args.width)
    b = (PipelineBuilder(cfg)
         .with_source(src)
         .with_sink(GraphStoreSink(node_cap=args.node_cap,
                                   edge_cap=args.edge_cap))
         .with_sketch(sketch_stage)
         .with_query_sink(depth=args.depth, width=args.width,
                          answer_every=args.query_every, top_k=5,
                          exact_topk=3 if args.mode == "live" else 0))
    if args.mode == "live":
        def on_sketch(ev):
            if ev.kind == "sketch":
                pairs = list(zip(ev.payload["hh_keys"], ev.payload["hh_counts"]))
                exact = ""
                if "exact_degrees" in ev.payload:
                    exact = " exact-deg: " + " ".join(
                        f"{k:#x}:{d}" for k, d in zip(ev.payload["exact_keys"],
                                                      ev.payload["exact_degrees"])
                        if k)
                print(f"[t={ev.t:7.1f}] live sketch: commits={ev.payload['commits']} "
                      f"absorbed={ev.payload['absorbed']} top: "
                      + " ".join(f"{k:#x}:{c}" for k, c in pairs if k) + exact)
        b = b.on_event(on_sketch)
    pipe = b.build()

    rep = pipe.run(max_ticks=args.ticks)
    store = pipe.store
    print(f"ingested: {rep.total_records} records -> "
          f"{int(store.n_nodes)} nodes, {int(store.n_edges)} edges "
          f"({rep.total_instructions} instructions)")

    # ---- snapshot + exact queries (incrementally maintained CSR) ----
    qsink0 = pipe.sink  # QuerySink
    t0 = time.perf_counter()
    snap = jax.block_until_ready(qsink0.snapshot())
    build_ms = (time.perf_counter() - t0) * 1e3
    m = qsink0.maintainer
    print(f"snapshot: {int(snap.n_nodes)} nodes, {int(snap.n_edges)} edges, "
          f"served in {build_ms:.1f} ms "
          f"(maintenance: {m.full_builds} full builds, "
          f"{m.delta_applies} delta applies)")
    dangling = int(store.n_edges) - int(snap.n_edges)
    if dangling:
        print(f"  ({dangling} edges dropped: endpoint node inserts failed — "
              f"node table at {int(store.n_nodes)}/{args.node_cap} load; "
              f"raise --node-cap)")

    hist = np.asarray(degree_distribution(snap, num_bins=16))
    print("degree distribution (bins 0..14, 15+):", hist.tolist())

    keys, degs = top_k_degree(snap, args.topk)
    keys, degs = np.asarray(keys), np.asarray(degs)
    qsink = pipe.sink  # QuerySink (commit-consistent sketch)
    sk_deg = sketch_stage.degree(keys)
    qs_deg = qsink.degree(keys)
    print(f"top-{args.topk} by degree (exact | sketch@filter | sketch@commit):")
    for k, d, s1, s2 in zip(keys, degs, sk_deg, qs_deg):
        if k:
            print(f"  node {int(k):#018x}  degree={int(d):5d}  "
                  f"sketch={int(s1):5d}  commit-sketch={int(s2):5d}")
    hh_k, hh_c = qsink.heavy_hitters(args.topk)
    overlap = len(set(hh_k[hh_k != 0].tolist()) & set(keys[keys != 0].tolist()))
    print(f"sketch heavy-hitter overlap with exact top-{args.topk}: "
          f"{overlap}/{args.topk} (additive error bound "
          f"{qsink.error_bound():.1f})")

    seed_key = keys[:1]
    n_reach = [int(np.asarray(k_hop(snap, seed_key, hops=h)).sum())
               for h in range(1, args.hops + 1)]
    print(f"k-hop from heaviest node: " +
          " ".join(f"{h+1}-hop={n}" for h, n in enumerate(n_reach)))

    if args.node_cap <= 4096:
        tri = int(triangle_count(snap))
        print(f"triangles: {tri}")

    # spot-check: sketch edge weights vs exact lookups on real edges
    live = np.asarray(snap.edge_row) < snap.node_cap
    nk = np.asarray(snap.node_key)
    take = np.flatnonzero(live)[:8]
    s_keys = nk[np.asarray(snap.edge_row)[take]]
    d_keys = nk[np.asarray(snap.edge_col)[take]]
    exact_w = np.asarray(edge_lookup(snap, s_keys, d_keys))
    est_w = qsink.edge_weight(s_keys, d_keys)
    print("edge-weight spot checks (exact vs sketch):",
          list(zip(exact_w.tolist(), est_w.tolist())))
    if args.dryrun:
        ok = (est_w >= exact_w).all() and int(snap.n_edges) > 0
        print(f"dryrun {'ok' if ok else 'FAILED'}")
        return 0 if ok else 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())

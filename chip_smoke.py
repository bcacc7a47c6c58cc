"""Drive the ingest-and-query main path once on one TPU chip.

    python chip_smoke.py

Two phases, both through `PipelineBuilder`, the entry point users call:

  ingest    `IngestConfig()` at its default capacities (2^20 node and
            2^21 edge slots, 8,192-edge batches, uint64 keys) over
            `BurstyTweetSource` at the full-stream rate, with the
            measured consumer.  A commit hook keeps host copies of the
            acknowledged batches; afterwards every store slot is read
            back and compared with a plain dict of (src, dst, etype) ->
            count, node degrees and node counts built from those copies.
  scenario  `ScenarioSource("flash_crowd")` with the filter-time sketch
            and dictionary compression.  The sketch must upper-bound the
            store's degrees, the dictionary store must equal a store fed
            the same batches through the raw commit, and the snapshot's
            degrees must equal the store's.

It exits non-zero when JAX finds no TPU, when it runs without the
repository around it, and when any check fails.  The lines before the
last are informational: implementation per hot op, compiles, host wall
seconds, commits and records.  The last line of stdout is one JSON
object naming the device.
"""
from __future__ import annotations

import collections
import dataclasses
import json
import sys
import tempfile
import time
from pathlib import Path

import jax

# exact 64-bit node identity, as `repro.launch.ingest` runs it
jax.config.update("jax_enable_x64", True)

import numpy as np

ROOT = Path(__file__).resolve().parent

INGEST_RATE = 6_000.0  # records/s: the full stream (ROADMAP W1)
INGEST_TICKS = 30
SCENARIO_TICKS = 60  # flash_crowd's 8x step lands at t = 30 s

BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


class CompileLog:
    """Backend compiles and persistent-cache hits seen by this process.

    A compile served from the cache still reports its (short) duration,
    so `seconds` is what compiling cost this run."""

    def __init__(self):
        self.compiles = 0
        self.seconds = 0.0
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == BACKEND_COMPILE:
            self.compiles += 1
            self.seconds += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def totals(self) -> dict:
        return {"compiles": self.compiles, "compile_s": self.seconds,
                "cache_hits": self.hits, "cache_misses": self.misses}


def _since(log: CompileLog, before: dict) -> dict:
    now = log.totals()
    return {k: now[k] - before[k] for k in now}


def _mismatches(want: dict, got: dict) -> int:
    """Keys whose values differ, counting keys missing on either side."""
    return sum(want.get(k) != got.get(k) for k in want.keys() | got.keys())


def _host_table(et) -> tuple:
    """Host copy of an acknowledged edge table's valid edges and nodes."""
    ev = np.asarray(et.edge_valid)
    nv = np.asarray(et.node_valid)
    return (np.asarray(et.src)[ev], np.asarray(et.dst)[ev],
            np.asarray(et.etype)[ev], np.asarray(et.count)[ev],
            np.asarray(et.node_ids)[nv], int(et.src.shape[0]))


def _read_store(store):
    """The device store as host dicts: edges -> count, node -> degree,
    node -> count."""
    s = jax.device_get(store)
    e = s.edge_keys != 0
    edges = dict(zip(zip(s.edge_src[e].tolist(), s.edge_dst[e].tolist(),
                         s.edge_type[e].tolist()), s.edge_count[e].tolist()))
    n = s.node_keys != 0
    keys = s.node_keys[n].tolist()
    degree = dict(zip(keys, s.node_degree[n].tolist()))
    count = dict(zip(keys, s.node_count[n].tolist()))
    return s, edges, degree, count


def _reference(acked) -> tuple:
    """The plain reference: edges, degrees and node counts from the
    acknowledged batches alone."""
    edges = collections.Counter()
    count = collections.Counter()
    for src, dst, ety, cnt, nodes, _cap in acked:
        for key, c in zip(zip(src.tolist(), dst.tolist(), ety.tolist()),
                          cnt.tolist()):
            edges[key] += c
        count.update(nodes.tolist())
    degree = collections.Counter()
    for s, d, _t in edges:
        degree[s] += 1
        degree[d] += 1
    return dict(edges), dict(degree), dict(count)


def _check(failures: list, ok: bool, what: str) -> None:
    print(f"  check {'ok  ' if ok else 'FAIL'} {what}")
    if not ok:
        failures.append(what)


def ingest_phase(cfg, rate: float, ticks: int, workdir: Path,
                 failures: list) -> dict:
    from repro.api import PipelineBuilder
    from repro.ingest.sources import BurstyTweetSource

    acked = []
    # uncontrolled (`launch.ingest --uncontrolled`): every tick commits
    # what arrived.  At this rate the paper-calibrated controller
    # predicts overload from the buffer size alone and spills every tick.
    pipe = (PipelineBuilder(cfg)
            .with_source(BurstyTweetSource(seed=0, mean_rate=rate))
            .uncontrolled()
            .measured_consumer()
            .spill_dir(str(workdir / "ingest_spill"))
            .build())
    ingestor = pipe.sink.ingestor
    ingestor.archive_dir = str(workdir / "ingest_archive")
    ingestor.commit_hooks.append(lambda et, _s: acked.append(_host_table(et)))
    rep = pipe.run(max_ticks=ticks)

    _, got_edges, got_degree, got_count = _read_store(pipe.store)
    want_edges, want_degree, want_count = _reference(acked)
    commits = ingestor.commits
    batch_edges = [len(a[0]) for a in acked]
    caps = sorted({a[5] for a in acked})
    print(f"  commits={len(acked)} records={rep.total_records} "
          f"edges mapped={rep.raw_instructions // 3} "
          f"unique per commit min={min(batch_edges, default=0)} "
          f"max={max(batch_edges, default=0)} table_caps={caps}")
    print(f"  store: {len(got_edges)} edges, {len(got_degree)} nodes")
    _check(failures, len(acked) >= 24, f"{len(acked)} commits (>= 24)")
    _check(failures, cfg.max_edges_per_batch in caps,
           f"full {cfg.max_edges_per_batch}-edge tables committed")
    _check(failures, all(c.ok for c in commits), "every commit acknowledged")
    dropped = sum(c.dropped for c in commits)
    _check(failures, dropped == 0, f"dropped_inserts == 0 (got {dropped})")
    n_bad = (_mismatches(want_edges, got_edges)
             + _mismatches(want_degree, got_degree)
             + _mismatches(want_count, got_count))
    _check(failures, n_bad == 0 and len(want_edges) > 0,
           f"read back {len(want_edges)} acknowledged edges and "
           f"{len(want_degree)} nodes: {n_bad} mismatches")
    return {"commits": len(acked), "records": rep.total_records,
            "edges": len(want_edges), "mismatches": n_bad,
            "dropped_inserts": dropped}


def scenario_phase(cfg, ticks: int, workdir: Path, failures: list) -> dict:
    from repro.api import PipelineBuilder
    from repro.api.stages import TransformStage
    from repro.graphstore.store import ingest_step, init_store
    from repro.query.snapshot import build_snapshot
    from repro.workloads import ScenarioSource

    encoded = collections.deque()

    class RecordingTransform(TransformStage):
        """The default transform, handing each table it encodes to the
        raw-path reference."""

        def encode(self, records):
            out = super().encode(records)
            encoded.append(out[0])
            return out

    raw = {"store": init_store(cfg.store_nodes, cfg.store_edges),
           "commits": 0}

    def commit_raw(_cc, _stats):
        # commits land in encode order: no faults are injected, so the
        # pool never holds a batch back
        raw["store"], _ = ingest_step(raw["store"], encoded.popleft())
        raw["commits"] += 1

    b = (PipelineBuilder(cfg)
         .with_source(ScenarioSource("flash_crowd", seed=0))
         .with_transform(RecordingTransform(
             max_edges_per_batch=cfg.max_edges_per_batch))
         .with_sketch()
         .with_compression()
         .measured_consumer()
         .spill_dir(str(workdir / "scenario_spill")))
    pipe = b.build()
    ingestor = pipe.sink.ingestor
    ingestor.archive_dir = str(workdir / "scenario_archive")
    ingestor.commit_hooks.append(commit_raw)
    rep = pipe.run(max_ticks=ticks)

    host, edges, degree, _ = _read_store(pipe.store)
    dstats = b.dictionary_stage.stats()
    commits = ingestor.commits
    print(f"  commits={len(commits)} records={rep.total_records} "
          f"store: {len(edges)} edges, {len(degree)} nodes; "
          f"dictionary refs={dstats['refs_total']} "
          f"entries={dstats['entries']}")
    _check(failures, len(commits) > 0 and all(c.ok for c in commits),
           f"{len(commits)} commits, every one acknowledged")
    dropped = sum(c.dropped for c in commits)
    _check(failures, dropped == 0, f"dropped_inserts == 0 (got {dropped})")
    _check(failures, dstats["refs_total"] > 0,
           "the dictionary committed pattern references")

    keys = np.asarray(list(degree), np.uint64)
    est = b.sketch_stage.degree(keys)
    store_deg = np.asarray(list(degree.values()))
    under = int((est < store_deg).sum())
    _check(failures, len(keys) > 0 and under == 0,
           f"sketch upper-bounds all {len(keys)} store degrees "
           f"({under} below)")

    ref = jax.device_get(raw["store"])
    diff = [f.name for f in dataclasses.fields(host)
            if not np.array_equal(getattr(host, f.name), getattr(ref, f.name))]
    _check(failures, raw["commits"] == len(commits) and not encoded
           and not diff,
           f"dictionary store equals the raw-commit store over "
           f"{raw['commits']} batches (differing fields: {diff or 'none'})")

    snap = jax.device_get(build_snapshot(pipe.store))
    n = int(snap.n_nodes)
    snap_degree = dict(zip(snap.node_key[:n].tolist(),
                           snap.node_degree[:n].tolist()))
    n_bad = _mismatches(degree, snap_degree)
    _check(failures, n_bad == 0 and int(snap.n_edges) == len(edges),
           f"snapshot degrees equal the store's ({n_bad} mismatches, "
           f"{int(snap.n_edges)} snapshot edges)")
    return {"commits": len(commits), "records": rep.total_records,
            "edges": len(edges), "dict_refs": dstats["refs_total"]}


def main() -> int:
    devices = jax.devices()
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices)}
    print(f"device: platform={device['platform']} kind={device['kind']} "
          f"count={device['count']}")
    if dev.platform != "tpu":
        print("chip_smoke: JAX found no TPU; refusing to run elsewhere",
              file=sys.stderr)
        return 1
    if not (ROOT / "src" / "repro").is_dir():
        print(f"chip_smoke: no repository around {ROOT}", file=sys.stderr)
        return 1
    sys.path.insert(0, str(ROOT / "src"))

    from repro.configs.paper_ingest import IngestConfig
    from repro.kernels import ops
    from repro.launch.compile_cache import enable_compile_cache

    print(f"compile cache: {enable_compile_cache()}")
    print("implementation per hot op: "
          + " ".join(f"{op}={impl}" for op, impl in ops.IMPL.items()))
    log = CompileLog()
    cfg = IngestConfig(mean_rate=INGEST_RATE)
    failures: list = []
    summary = {}
    with tempfile.TemporaryDirectory(prefix=".chip_smoke_", dir=ROOT) as tmp:
        for name, run in (
                ("ingest", lambda: ingest_phase(cfg, INGEST_RATE, INGEST_TICKS,
                                                Path(tmp), failures)),
                ("scenario", lambda: scenario_phase(IngestConfig(),
                                                    SCENARIO_TICKS, Path(tmp),
                                                    failures))):
            print(f"phase {name}:")
            before, t0 = log.totals(), time.perf_counter()
            summary[name] = run()
            spent = _since(log, before)
            print(f"  host wall {time.perf_counter() - t0:.3f} s; "
                  f"compiles={spent['compiles']} "
                  f"compile_s={spent['compile_s']:.3f} "
                  f"cache hits={spent['cache_hits']} "
                  f"misses={spent['cache_misses']}")
    print(f"totals: {json.dumps({**summary, **log.totals()})}")
    if failures:
        print(f"chip_smoke: {len(failures)} check(s) failed: {failures}",
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""One run of one cell: set-up, the measured window, the drain, the
check against the plain reference, and the result line.

The window drives the program's own entry, `StreamPipeline.run`, built
by `PipelineBuilder`, from `OpenLoopPoll`: an open-loop stream consumer
that hands the pipeline, on each pull, every event due by then, up to the
configuration's poll cap.  Events due and not yet pulled wait in the
harness's queue, and their wait counts in their latency.  The harness
wraps the pipeline's transform, sink and record stages only to write
`chipbench.*` spans into the profiler's trace and to note which events
each commit carried."""
from __future__ import annotations

import dataclasses
import gc
import json
import math
import os
import sys
import tempfile
import time
from pathlib import Path
from typing import Dict, List, Optional

import numpy as np

from chipbench import prefill, reference
from chipbench.compile_log import LoweredNames
from chipbench.manifest import Cell, load_cell
from chipbench.traffic import Events, generate

WARM_STREAM, WINDOW_STREAM = 1, 0
DRAIN_TICK_S = 0.02  # pause between empty ticks while the pipeline drains
# on the dictionary path, the warm-up's second pass repeats these shares
# of the events just fed, and its third repeats each of `WIDE_SHARES` at
# every power-of-two size from 64 events (see `warm_up`)
REPLAY_SHARES = (0.25, 0.5, 0.75, 1.0)
WIDE_SHARES = (0.02, 0.05, 0.1, 0.25, 0.5, 0.75, 1.0)


def log(*a) -> None:
    print(*a, file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# what the window drives
# ---------------------------------------------------------------------------


class Ledger:
    """Which events each acknowledged commit carried, and when."""

    def __init__(self, n_events: int, clock):
        self.clock = clock
        self.ack = np.full(n_events, np.nan)
        self.batches: List[np.ndarray] = []
        self.lanes: List[tuple] = []  # (edge lanes, key bytes) per commit
        self._pending: Dict[int, tuple] = {}

    def encoded(self, table, records) -> None:
        self._pending[id(table)] = (
            table, np.fromiter((r["eid"] for r in records), np.int64,
                               len(records)))

    def on_commit(self, table, _stats) -> None:
        entry = self._pending.pop(id(table), None)
        if entry is None:
            return
        eids = entry[1]
        self.ack[eids] = self.clock()
        self.batches.append(eids)
        upserted = getattr(table, "residual", table)
        self.lanes.append((int(upserted.src.shape[0]),
                           int(upserted.src.dtype.itemsize)))


class TracedTransform:
    def __init__(self, inner, ledger: Ledger):
        import jax

        self.inner, self.ledger, self.name = inner, ledger, inner.name
        self._ann = jax.profiler.TraceAnnotation

    def encode(self, records):
        with self._ann("chipbench.transform"):
            out = self.inner.encode(records)
        self.ledger.encoded(out[0], records)
        return out


class TracedSink:
    def __init__(self, inner):
        import jax

        self.inner = inner
        self._ann = jax.profiler.TraceAnnotation

    def commit(self, et, now=None):
        with self._ann("chipbench.commit"):
            return self.inner.commit(et, now=now)

    def __getattr__(self, name):  # ingestor, store, retry_archive, ...
        return getattr(self.inner, name)


class TracedStage:
    def __init__(self, inner):
        import jax

        self.inner, self.name = inner, inner.name
        self._label = f"chipbench.stage.{inner.name}"
        self._ann = jax.profiler.TraceAnnotation

    def __call__(self, records, ctx=None):
        with self._ann(self._label):
            return self.inner(records, ctx)


class OpenLoopPoll:
    """The stream consumer.  Each pull yields one `StreamTick` whose `t`
    is the wall second since the window opened, holding every event due
    by then up to `cap`; with nothing due it sleeps until the next event
    is.  At `seconds` the window closes: no more events are offered, and
    empty ticks follow until every pulled event is acknowledged or
    `drain_s` has passed."""

    def __init__(self, events: Events, records: List[dict], cap: int,
                 seconds: float, drain_s: float, ledger: Ledger, on_close):
        import jax

        self.due, self.records, self.cap = events.due, records, cap
        self.seconds, self.drain_s = seconds, drain_s
        self.ledger, self.on_close = ledger, on_close
        self.next = 0          # first event not yet pulled
        self.pulled_in_window = 0
        self.ticks_in_window = 0
        self.closed = False
        self.t_open = None
        self._ann = jax.profiler.TraceAnnotation

    def now(self) -> float:
        return time.perf_counter() - self.t_open

    def __iter__(self):
        return self

    def __next__(self):
        from repro.ingest.sources import StreamTick

        with self._ann("chipbench.poll"):
            now = self.now()
            if not self.closed and now < self.seconds:
                if self.next < len(self.due) and self.due[self.next] > now:
                    time.sleep(max(0.0, min(self.due[self.next],
                                            self.seconds) - now))
                    now = self.now()
            if not self.closed and now < self.seconds:
                j = int(np.searchsorted(self.due, now, side="right"))
                take = max(0, min(j - self.next, self.cap))
                batch = self.records[self.next:self.next + take]
                self.next += take
                self.pulled_in_window += take
                self.ticks_in_window += 1
                return StreamTick(now, batch)
            if not self.closed:
                self.closed = True
                self.on_close()
            pulled = self.ledger.ack[:self.next]
            if not np.isnan(pulled).any() or now > self.seconds + self.drain_s:
                raise StopIteration
            time.sleep(DRAIN_TICK_S)
            return StreamTick(self.now(), [])


# ---------------------------------------------------------------------------
# set-up
# ---------------------------------------------------------------------------


def build_pipeline(cfg: Dict, workdir: str, telemetry: bool):
    from repro.api import PipelineBuilder
    from repro.configs.paper_ingest import IngestConfig

    path = cfg["path"]
    b = (PipelineBuilder(IngestConfig(**cfg.get("ingest", {})))
         .measured_consumer()
         .spill_dir(os.path.join(workdir, "spill")))
    if not path["controlled"]:
        b.uncontrolled()
    if path["sketch"]:
        b.with_sketch()
    if path["dictionary"]:
        b.with_compression()
    if telemetry:
        b.with_telemetry()
    pipe = b.build()
    pipe.sink.ingestor.archive_dir = os.path.join(workdir, "archive")
    return pipe, b


def warm_sizes(cfg: Dict) -> List[int]:
    """Tick sizes (events) the warm-up feeds.

    The first is the window's first tick: a full poll where the consumer
    starts on a backlog, else one event.  A program's first call on fresh
    state (an empty store, sketch or dictionary) is a separate entry in
    JAX's cache, so it has to be warmed at the window's first shape.
    Then every size up to `each_up_to`, then every power of two up to the
    poll cap, so that each table capacity from 64 to the batch cap
    compiles in set-up."""
    cap = int(cfg["poll_max_events"])
    backlog = float(cfg.get("consumer_lag_s", 0.0)) * cfg["rate_events_per_s"]
    sizes = [min(cap, max(1, int(backlog)))]
    sizes += range(1, int(cfg["warm_events"]["each_up_to"]) + 1)
    p = 1
    while p <= cap:
        sizes.append(p)
        p *= 2
    return sizes


def warm_up(cfg: Dict, events: Events, workdir: str,
            telemetry: bool) -> tuple:
    """Drive a throw-away pipeline, built as the window's is, through
    ticks of every size the window can see.  Ticks lie 100 s apart in
    stream time, so that the controller's occupancy window never holds
    a compile and it pushes on every tick, as at the window's rates.

    On the dictionary path a second pass feeds the sizes again, each
    tick repeating one of `REPLAY_SHARES` of the events fed just before
    it, in turn, and a third feeds every power-of-two size from 64
    events once with each of `WIDE_SHARES` repeated.  Repeated events
    hit the pattern dictionary, and the dictionary path's shapes depend
    on how many edges of a table hit, so this warms its split and commit
    at the mixes of hits and misses that a tick of the window can bring,
    large batches that the controller held among them.  Last, the
    batches' padding is warmed at every count (`warm_pads`).  Returns the
    ticks fed and the counts padded."""
    from repro.ingest.sources import StreamTick

    pipe, _ = build_pipeline(cfg, workdir, telemetry)
    recs = events.records()
    sizes = warm_sizes(cfg)
    plan = [(k, 0.0) for k in sizes]
    if cfg["path"]["dictionary"]:
        plan += [(k, REPLAY_SHARES[j % len(REPLAY_SHARES)])
                 for j, k in enumerate(sizes)]
        plan += [(k, share) for k in sorted(set(sizes))
                 if k >= 64 and k & (k - 1) == 0 for share in WIDE_SHARES]
    fed: List[dict] = []
    ticks, i = [], 0
    for j, (k, share) in enumerate(plan):
        again = round(share * k)
        if i + k - again > len(recs):
            i = 0
        batch = fed[len(fed) - again:] + recs[i:i + k - again]
        i += k - again
        fed = (fed + batch)[-max(sizes):]
        ticks.append(StreamTick(1000.0 + 100.0 * j, batch))
    pipe.run(source_ticks=ticks, max_ticks=len(ticks))
    import jax

    jax.block_until_ready(pipe.store.n_nodes)
    return len(ticks), warm_pads(cfg, pipe, recs)


def warm_pads(cfg: Dict, pipe, recs: List[dict]) -> int:
    """`from_raw_batch` pads a batch to its table's capacity with eager
    programs, one set for each raw edge count.  A batch that the
    controller held can bring any count, so each count of whole events
    above `each_up_to`, up to `pads_up_to`, is padded once here through
    the program's own `from_raw_batch`, at the capacity the transform
    would pick; a larger batch is cut to the batch cap, a shape warmed at
    the top.  Returns the number of counts warmed."""
    import jax
    from repro.core.edge_table import from_raw_batch
    from repro.core.transform import create_edges
    from repro.configs.paper_ingest import IngestConfig

    top = min(int(cfg["warm_events"].get("pads_up_to", 0)), len(recs))
    lo = int(cfg["warm_events"]["each_up_to"]) + 1
    if top < lo:
        return 0
    transform = getattr(pipe.transform, "inner", pipe.transform)
    raw = create_edges(recs[:top], transform.mapping)
    per = raw.n_edges // top  # edges per event: four under Fig. 6 here
    cap_max = IngestConfig(**cfg.get("ingest", {})).max_edges_per_batch
    et = None
    for k in range(lo, top + 1):
        n = k * per
        cap = min(max(64, 1 << (n - 1).bit_length()), cap_max)
        et = from_raw_batch(dataclasses.replace(
            raw, src=raw.src[:n], dst=raw.dst[:n], etype=raw.etype[:n],
            src_type=raw.src_type[:n], dst_type=raw.dst_type[:n]), cap)
    jax.block_until_ready(et.n_edges)
    return top - lo + 1


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class RunContext:
    """What the metric readers (`chipbench/metrics/*.py`) read."""

    cell: Cell
    seconds: float
    setup_s: float
    due: np.ndarray            # due time of every event, s
    ack: np.ndarray            # ack time, s; NaN where never acknowledged
    end_s: float               # when the drain ended, s
    pulled: int                # events handed to the pipeline
    pulled_in_window: int
    ticks_in_window: int
    commits: list              # CommitRecords of the window
    lanes: list                # (edge lanes, key bytes) per window commit
    batches: list              # event indices per window commit
    spans: list                # program telemetry (name, t0_s, t1_s), window
    decisions: Dict[str, int]  # controller decisions in the window
    trace: Optional[object]    # trace.Reduced, traced runs only
    peaks: Optional[Dict]

    def latencies_ms(self) -> np.ndarray:
        return latencies_ms(self.due, self.ack, self.seconds, self.end_s)

    def events_committed(self) -> int:
        """Events carried by the commits of the window."""
        return int(sum(len(b) for b in self.batches))

    def span_total(self, name: str) -> float:
        return sum(b - a for n, a, b in self.spans if n == name)

    def span_self(self, name: str) -> tuple:
        """(self seconds, count) of a span: its length less the part
        covered by other spans inside it."""
        from chipbench.trace import length, merge

        outer = [(a, b) for n, a, b in self.spans if n == name]
        inner = sorted((a, b) for n, a, b in self.spans if n != name)
        total, k = 0.0, 0
        for a, b in sorted(outer):
            while k < len(inner) and inner[k][0] < a:
                k += 1
            kids, j = [], k
            while j < len(inner) and inner[j][0] < b:
                kids.append(inner[j])
                j += 1
            total += (b - a) - length(merge(kids, a, b))
        return total, len(outer)


def latencies_ms(due: np.ndarray, ack: np.ndarray, seconds: float,
                 end_s: float) -> np.ndarray:
    """Due-to-acknowledged latency of every event due in the window; an
    event never acknowledged counts as acknowledged when the run gave up
    on it, above every acknowledged one."""
    inw = (due >= 0) & (due < seconds)
    ack = np.where(np.isnan(ack), end_s + 1.0, ack)
    return (ack[inw] - due[inw]) * 1e3


def percentile(values: np.ndarray, q: float) -> float:
    """Nearest-rank percentile (q in (0, 100])."""
    v = np.sort(values)
    return float(v[max(0, math.ceil(q / 100.0 * len(v)) - 1)])


def sketch_degrees(sketch_stage, keys: List[int]) -> np.ndarray:
    """The sketch's degree estimates, in fixed blocks of keys."""
    block = 8192
    out = []
    for lo in range(0, len(keys), block):
        chunk = np.zeros(block, np.uint64)
        part = np.asarray(keys[lo:lo + block], np.uint64)
        chunk[:len(part)] = part
        out.append(sketch_stage.degree(chunk)[:len(part)])
    return np.concatenate(out) if out else np.zeros(0, np.int64)


def check(events: Events, ledger: Ledger, pulled: int, store,
          past: prefill.Past, sketch_stage) -> Dict[str, Dict]:
    """Every number compared, with its limit.  The guarantee is exact:
    every event handed to the pipeline is acknowledged, and each
    acknowledged event's edges read back with their counts, each node's
    degree and count as the plain reference has them.  The past that
    set-up committed is held to its totals: the store's totals are the
    past's plus the window's."""
    want_edges, want_degree, want_count = reference.build(events,
                                                          ledger.batches)
    (got_edges, got_degree, got_count), got_totals = prefill.select(
        store, past)
    window = {"nodes": len(want_degree.keys() | want_count.keys()),
              "edges": len(want_edges),
              "edge_count_sum": sum(want_edges.values()),
              "node_count_sum": sum(want_count.values()),
              "degree_sum": sum(want_degree.values())}
    expected = past.totals()
    totals_off = sum(got_totals[k] != expected[k] + window[k] for k in window)
    unacked = int(np.isnan(ledger.ack[:pulled]).sum())
    checks = {
        "events_unacknowledged": {"value": unacked, "limit": 0},
        "edges_mismatched": {
            "value": reference.mismatches(want_edges, got_edges), "limit": 0},
        "degrees_mismatched": {
            "value": reference.mismatches(want_degree, got_degree),
            "limit": 0},
        "counts_mismatched": {
            "value": reference.mismatches(want_count, got_count), "limit": 0},
        "totals_mismatched": {"value": totals_off, "limit": 0},
    }
    if sketch_stage is not None:
        wd = reference.weighted_degree(want_edges)
        keys = list(wd)
        est = sketch_degrees(sketch_stage, keys)
        under = int((est < np.asarray([wd[k] for k in keys])).sum())
        checks["sketch_degrees_under"] = {"value": under, "limit": 0}
    return checks


def passes(checks: Dict[str, Dict]) -> bool:
    return all(c["value"] <= c["limit"] for c in checks.values())


def run(root: Path, workload: str, seed: int, seconds: float, trace: bool,
        t_start: float, log_compiles, device: Dict,
        require_tpu: bool = True) -> Dict:
    """One run; returns the result line's object and the number of
    programs compiled or loaded inside the window.  `t_start` is when the
    process started, so that set-up counts imports too."""
    import jax

    cell = load_cell(root, workload)
    cfg = cell.config
    peaks = None
    if require_tpu:
        from chipbench.roofline import peaks as peak_table

        peaks = peak_table(device["kind"])
    split: Dict[str, float] = {}
    c0 = log_compiles.totals()

    t = time.perf_counter()
    lead = float(cfg.get("consumer_lag_s", 0.0))
    events = generate(cell.traffic, cfg, seed, seconds, lead_s=lead,
                      stream=WINDOW_STREAM)
    records = events.records()
    warm_events = generate(cell.traffic, cfg, seed, seconds,
                           stream=WARM_STREAM)
    split["generate_s"] = time.perf_counter() - t

    with tempfile.TemporaryDirectory(prefix="chipbench_") as workdir:
        t = time.perf_counter()
        n_warm, n_pads = warm_up(cfg, warm_events,
                                 os.path.join(workdir, "warm"),
                                 telemetry=trace)
        del warm_events
        gc.collect()  # the throw-away store must be gone before the next
        split["warm_up_s"] = time.perf_counter() - t
        t = time.perf_counter()
        pipe, builder = build_pipeline(cfg, os.path.join(workdir, "run"),
                                       telemetry=trace)
        jax.block_until_ready(pipe.store.n_nodes)
        split["store_alloc_s"] = time.perf_counter() - t
        t = time.perf_counter()
        ingestor = pipe.sink.ingestor
        ingestor.store, past = prefill.fill(ingestor.store, cfg, seed)
        split["prefill_s"] = time.perf_counter() - t
        warm = log_compiles.since(c0)

        ledger = Ledger(len(events), clock=lambda: poll.now())
        pipe.transform = TracedTransform(pipe.transform, ledger)
        pipe.sink = TracedSink(pipe.sink)
        pipe.stages = [TracedStage(s) for s in pipe.stages]
        ingestor.commit_hooks.append(ledger.on_commit)
        registry = pipe.telemetry
        log_dir = os.path.join(workdir, "trace")
        window_ann = None  # made once the trace runs: an annotation made
        # before `start_trace` is never recorded
        at_close: Dict = {}

        lowered = LoweredNames()

        def close():
            lowered.__exit__(None, None, None)
            at_close["compiles"] = log_compiles.since(c_open)
            at_close["commits"] = len(ingestor.commits)
            at_close["lanes"] = len(ledger.lanes)
            at_close["decisions"] = dict(pipe.controller.decision_counts)
            at_close["ns"] = time.perf_counter_ns()
            if trace:
                window_ann.__exit__(None, None, None)
                jax.profiler.stop_trace()

        poll = OpenLoopPoll(events, records, int(cfg["poll_max_events"]),
                            seconds, float(cfg["drain_limit_s"]), ledger,
                            close)
        setup_s = time.perf_counter() - t_start
        print(f"setup: {setup_s:.3f} s = process start to window; "
              + " ".join(f"{k}={v:.3f}" for k, v in split.items())
              + f"; warm-up ticks={n_warm} pad counts={n_pads} "
              f"compiles={warm['compiles']} "
              f"compile_s={warm['compile_s']:.3f} "
              f"cache_hits={warm['cache_hits']} "
              f"cache_misses={warm['cache_misses']}; prefill nodes="
              f"{past.nodes} edges={past.edges} dropped={past.dropped}",
              flush=True)

        if trace:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(log_dir, profiler_options=opts)
            window_ann = jax.profiler.TraceAnnotation("chipbench.window")
        c_open = log_compiles.totals()
        ns_open = time.perf_counter_ns()
        if trace:
            window_ann.__enter__()
        poll.t_open = time.perf_counter()
        with lowered:
            pipe.run(source_ticks=poll, max_ticks=1 << 62)
        end_s = poll.now()
        in_window = at_close["compiles"]
        print(f"window: {seconds:.3f} s, compiles inside it: "
              f"{in_window['compiles']} (cache hits {in_window['cache_hits']}"
              f", misses {in_window['cache_misses']}); events pulled "
              f"{poll.next}, acknowledged "
              f"{int((~np.isnan(ledger.ack)).sum())}; drain ended at "
              f"{end_s:.3f} s", flush=True)
        due_by_close = int(np.searchsorted(events.due, seconds))
        lat = np.sort(latencies_ms(events.due, ledger.ack, seconds, end_s))
        print(f"load: due by the close {due_by_close}, pulled in the window "
              f"{poll.pulled_in_window} over {poll.ticks_in_window} ticks, "
              f"acknowledged in the window "
              f"{int(np.sum(ledger.ack <= seconds))}; latency ms p50 "
              f"{percentile(lat, 50):.3f} p95 {percentile(lat, 95):.3f} "
              f"max {lat[-1]:.3f}", flush=True)
        if lowered.names:
            print("lowered inside the window: " + "; ".join(
                f"{n} x{c}" for n, c in lowered.names.most_common(12)),
                flush=True)

        stats = jax.devices()[0].memory_stats() or {}
        device["memory_peak_bytes"] = int(stats.get("peak_bytes_in_use", 0))

        spans = [(n, (t0 - ns_open) * 1e-9, (t1 - ns_open) * 1e-9)
                 for n, _s, t0, t1 in registry.events
                 if t0 >= ns_open and t1 <= at_close["ns"]]
        ctx = RunContext(
            cell=cell, seconds=seconds, setup_s=setup_s, due=events.due,
            ack=ledger.ack, end_s=end_s, pulled=poll.next,
            pulled_in_window=poll.pulled_in_window,
            ticks_in_window=poll.ticks_in_window,
            commits=ingestor.commits[:at_close["commits"]],
            lanes=ledger.lanes[:at_close["lanes"]],
            batches=ledger.batches[:at_close["lanes"]], spans=spans,
            decisions=at_close["decisions"], trace=None, peaks=peaks)

        store = pipe.store
        sketch_stage = builder.sketch_stage
        del pipe, ingestor
        checks = check(events, ledger, poll.next, store, past, sketch_stage)
        del store

        if trace:
            from chipbench import trace as tr

            loaded = tr.load(tr.find_xplane(log_dir))
            if loaded.n_chips or require_tpu:
                ctx.trace = tr.Reduced(loaded)
                device["busy_s"] = ctx.trace.busy_s
                device["window_s"] = ctx.trace.window_s
            else:
                log("chipbench: the trace holds no TPU plane; no device "
                    "metric is reported")

    metrics_of = cell.per_layer if trace else cell.end_to_end
    metrics = {}
    for m in metrics_of:
        v = m.reader(ctx)
        if v is not None:
            metrics[m.name] = {"value": v, "unit": m.unit}
    acked = int((~np.isnan(ledger.ack[:poll.next])).sum())
    out = {"correct": passes(checks), "attempted": int(poll.next),
           "failed": int(poll.next - acked), "metrics": metrics,
           "device": device}
    if ctx.trace is not None:
        out["breakdown"] = {"device_ops": ctx.trace.device_ops(),
                            "idle_gaps": ctx.trace.idle_gaps()}
    out["checks"] = checks
    return out, in_window["compiles"]

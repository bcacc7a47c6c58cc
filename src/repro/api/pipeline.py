"""`StreamPipeline`: the paper's closed control loop over pluggable parts.

Each tick: Source -> FilterStage -> BufferControlStage; the controller
(Algorithm 2) decides push/hold/throttle/drain from the predictive
models; pushed buckets go through TransformStage (Algorithm 1 + graph
compression) into the Sink (Algorithm 3 GRAPHPUSH), and the Consumer
absorbs the instruction load and reports occupancy mu back to the
controller.  `uncontrolled=True` bypasses the controller — the paper's
meltdown baseline (Figs. 1-3, 7).

The loop itself is the only fixed part; every box is swappable via the
constructor (or `PipelineBuilder`).  `TickLoop.run` is the one tick
loop of every pipeline (`ShardedPipeline` included), and `push_batch`
the one push body of the uncontrolled and the controlled tick.
"""
from __future__ import annotations

import time
from typing import Iterable, Optional, Sequence

from repro.api.consumers import SimulatedConsumer
from repro.api.metrics import MetricsHub, PipelineReport
from repro.api.protocols import Source, TickContext
from repro.api.sinks import GraphStoreSink
from repro.api.stages import BufferControlStage, FilterStage, TransformStage
from repro.configs.paper_ingest import IngestConfig
from repro.core.buffer import PerfSample


def maybe_retry_archive(sink, hub: MetricsHub, now: float) -> int:
    """Backoff-governed archive replay (repro.resilience): runs every
    tick, but ONLY when the sink's ingestor carries a `RetryPolicy` —
    legacy pipelines (no policy) keep the manual `retry_archive()`
    surface and never auto-retry.  The policy's gate makes this cheap:
    while the backoff window is open the call returns without touching
    the store, so a dead connection is probed exponentially rarely
    instead of once per tick."""
    ing = getattr(sink, "ingestor", None)
    if ing is None or getattr(ing, "retry_policy", None) is None:
        return 0
    if not getattr(ing, "archive_depth", 0):
        return 0
    with hub.telemetry.span("retry.archive"):
        n = sink.retry_archive(now) if hasattr(sink, "retry_archive") \
            else ing.retry_archive(now)
    if n:
        hub.emit("retry", now, replayed=n, remaining=ing.archive_depth)
    return n


def fetch_table_stats(tel, et):
    """The committed table's compression ratio, size and density as host
    floats: the loop's device-to-host pulls, read once per tick inside
    one `loop.fetch` span."""
    with tel.span("loop.fetch"):
        return (float(et.compression_ratio()), float(et.size()),
                float(et.density()))


def new_loop_state(cfg: IngestConfig) -> dict:
    """The cross-tick loop scalars of one buffer: last_beta_e/last_mu
    for the mu-model updates, and the records/instr/raw/crs totals."""
    return {"last_beta_e": cfg.beta_init, "last_mu": 0.0,
            "records": 0, "instr": 0, "raw": 0, "crs": []}


def intake(buf: BufferControlStage, state: dict, records, now: float):
    """Count a tick's records into `state` and the rate model, and
    buffer them."""
    state["records"] += len(records)
    buf.perfmon.observe_rate(now, len(records))
    buf.extend(records)


# what a failed commit reports in its `commit-failed` event
_FAILED = {"rho": 1.0, "dropped": 0, "probe_rounds": 0, "pressure": 0.0,
           "refs": 0, "dict_hit_rate": None}


def push_batch(batch, buf: BufferControlStage, transform, sink, consumer,
               hub: MetricsHub, state: dict, now: float, cdt: float):
    """Commit one taken batch and account for it: the one push body of
    every path.  Lineage custody, encode, commit, the consumer (`cdt`
    seconds of its capacity), the table's stats, the commit events and
    the `state` totals.  Returns `(out, mu, rho, cr, size, density)`:
    the sink's outcome and what the caller records."""
    tel = hub.telemetry
    lineage = getattr(hub, "lineage", None)
    tag = handed = None
    if lineage is not None:
        tag = lineage.open_batch(
            batch, now, shard=getattr(tel, "shard", None),
            spilled=buf.last_take_spilled)
    et, n_instr, raw_i = transform.encode(batch)
    if tag is not None:
        handed = lineage.stage_commit(tag, sink)
    out = sink.commit(et, now=now)
    if tag is not None:
        lineage.after_commit(tag, out, now, handed=handed)
    with tel.span("consume"):
        mu = consumer.consume(n_instr, cdt, now=now)
    cr, size, density = fetch_table_stats(tel, et)
    s = out if out["committed"] else _FAILED
    hit = s["dict_hit_rate"]
    hub.emit("commit" if out["committed"] else "commit-failed", now,
             instructions=n_instr, raw=raw_i, rho=s["rho"], cr=cr,
             dropped=s["dropped"], probe_rounds=s["probe_rounds"],
             pressure=s["pressure"], refs=s["refs"],
             dict_hit_rate=0.0 if hit is None else hit)
    if "pool_overflow" in out:
        hub.emit("pool_overflow", now, total=out["pool_overflow"])
    if "degraded" in out:
        hub.emit("degraded", now, archived=out["archived"])
    state["instr"] += n_instr
    state["raw"] += raw_i
    state["crs"].append(cr)
    return out, mu, s["rho"], cr, size, density


def uncontrolled_tick(buf: BufferControlStage, transform, sink, consumer,
                      hub: MetricsHub, state: dict, now: float, dt: float):
    """One tick with no controller (paper Figs. 1-3/7): push the whole
    buffer every tick."""
    pm = buf.perfmon
    if len(buf):
        batch = buf.take_all()
        out, mu, rho, cr, size, density = push_batch(
            batch, buf, transform, sink, consumer, hub, state, now, dt)
        pm.observe_mu(mu)
        hub.emit("push", now, records=len(batch))
        hub.record(PerfSample(now, mu, rho, density, len(buf), size,
                              *pm.velocity(), "push", buf.spill_depth, cr,
                              consumer.delay_s))
    maybe_retry_archive(sink, hub, now)


def controlled_tick(buf: BufferControlStage, transform, sink, consumer,
                    hub: MetricsHub, state: dict, now: float, dt: float,
                    consume_dt: Optional[float] = None):
    """One controlled tick (Algorithm 2 steps 2-7) on one buffer.

    Shared by `StreamPipeline` (one buffer) and `ShardedPipeline` (one
    call per shard) so the loop semantics cannot drift between them.
    `consume_dt` is the slice of the tick this buffer may drain from
    the consumer — dt/n_shards when N buffers share one consumer.
    `state` carries the cross-tick scalars (`new_loop_state`).
    """
    cdt = dt if consume_dt is None else consume_dt
    tel = hub.telemetry
    pm = buf.perfmon
    aud = buf.controller.audit
    with tel.span("decide"):
        dec = buf.decide(len(buf) * 4.0, 0.0, now=now)

    if dec.action in ("push", "drain+push") and len(buf) >= 1:
        if dec.action == "drain+push" and buf.spill_depth:
            with tel.span("spill.drain"):
                buf.drain_spill()
            hub.emit("drain", now, depth=buf.spill_depth)
        batch = buf.take_batch()
        if batch:
            out, mu, rho, cr, size, density = push_batch(
                batch, buf, transform, sink, consumer, hub, state, now, cdt)
            if out["committed"]:
                # table pressure -> Algorithm-2 controller (back-pressure)
                pm.observe_pressure(out["pressure"], out["dropped"])
                if out["dict_hit_rate"] is not None:
                    # compressibility -> the controller's "data content"
                    # input (dictionary compression, repro.compress)
                    pm.observe_compression(out["dict_hit_rate"], cr)
            pm.observe_mu(mu)
            if aud is not None:
                # predicted-vs-realized for the audit trail
                aud.resolve(mu, size)
            pm.observe_bucket(rho, density, size)
            pm.observe_mu_outcome(state["last_mu"], state["last_beta_e"], mu)
            state["last_beta_e"], state["last_mu"] = size, mu
            hub.emit("push", now, records=len(batch))
            hub.record(PerfSample(now, mu, rho, density,
                                  len(buf), size,
                                  *pm.velocity(), dec.action,
                                  buf.spill_depth, cr, consumer.delay_s))
    elif dec.action == "throttle":
        # spill the whole buffer to disk (data throttling)
        if len(buf):
            with tel.span("spill.flush"):
                buf.spill_all()
            hub.emit("spill", now, depth=buf.spill_depth)
        mu = consumer.consume(0, cdt, now=now)
        pm.observe_mu(mu)
        if aud is not None:
            aud.resolve(mu, 0.0)
        hub.emit("throttle", now)
        hub.record(PerfSample(now, mu, 0.0, 0.0, 0,
                              dec.beta_e, *pm.velocity(),
                              "throttle", buf.spill_depth, 1.0,
                              consumer.delay_s))
    else:  # hold
        mu = consumer.consume(0, cdt, now=now)
        pm.observe_mu(mu)
        if aud is not None:
            aud.resolve(mu, 0.0)
        hub.emit("hold", now, buffered=len(buf))
        hub.record(PerfSample(now, mu, 0.0, 0.0, len(buf),
                              dec.beta_e, *pm.velocity(),
                              "hold", buf.spill_depth, 1.0,
                              consumer.delay_s))

    # archived batches replay on every action (the connection may be
    # back while the controller holds/throttles) — policy-gated, above
    maybe_retry_archive(sink, hub, now)


class TickLoop:
    """The part every pipeline shares: its parts, the tick prologue
    (filter, extra stages, the `tick` event) and the common part of its
    checkpoint.  A subclass supplies `_step` (the work on one tick's
    filtered records), `_report` and its own checkpoint keys."""

    def __init__(self, cfg, source, filter_stage, transform, consumer,
                 sink, metrics, stages):
        self.cfg = cfg or IngestConfig()
        self.source = source
        self.filter_stage = filter_stage or FilterStage()
        self.stages = list(stages)  # extra Stage-protocol record stages
        self.transform = transform or TransformStage(
            max_edges_per_batch=self.cfg.max_edges_per_batch)
        self.consumer = consumer or SimulatedConsumer()
        self.sink = sink or GraphStoreSink(
            node_cap=self.cfg.store_nodes, edge_cap=self.cfg.store_edges)
        self.metrics = metrics or MetricsHub()
        self.telemetry = self.metrics.telemetry

    @property
    def store(self):
        return self.sink.store

    def run(self, source_ticks: Optional[Iterable] = None,
            max_ticks: int = 300):
        if source_ticks is None:
            if self.source is None:
                raise ValueError("no source: pass source_ticks or set source")
            source_ticks = self.source.ticks()
        t_start = time.time()
        tel = self.telemetry
        for i, tick in enumerate(source_ticks):
            if i >= max_ticks:
                break
            now, dt = tick.t, 1.0
            ctx = TickContext(t=now, dt=dt, index=i)
            with tel.span("tick"):
                with tel.span("filter"):
                    recs = self.filter_stage(tick.records, ctx)
                for stage in self.stages:
                    recs = stage(recs, ctx)
                self.metrics.emit("tick", now, raw=len(tick.records),
                                  kept=len(recs))
                self._step(recs, now, dt)
        return self._report(time.time() - t_start)

    # ---- checkpoint surface (repro.resilience) -----------------------
    def state(self) -> dict:
        """Host-side resumable state: everything the checkpointer's
        array manifest does not cover (see resilience/checkpoint.py)."""
        s: dict = {
            "metrics": self.metrics.state(),
            "stages": [st.state() if hasattr(st, "state") else None
                       for st in self.stages],
        }
        if hasattr(self.consumer, "state"):
            s["consumer"] = self.consumer.state()
        if hasattr(self.sink, "state"):
            s["sink"] = self.sink.state()
        tracker = getattr(self.metrics, "lineage", None)
        if tracker is not None:
            s["lineage"] = tracker.state()
        return s

    def restore_state(self, s: dict) -> None:
        self.metrics.restore_state(s["metrics"])
        for st, st_s in zip(self.stages, s["stages"]):
            if st_s is not None and hasattr(st, "restore_state"):
                st.restore_state(st_s)
        if "consumer" in s and hasattr(self.consumer, "restore_state"):
            self.consumer.restore_state(s["consumer"])
        if "sink" in s and hasattr(self.sink, "restore_state"):
            self.sink.restore_state(s["sink"])
        tracker = getattr(self.metrics, "lineage", None)
        if tracker is not None and "lineage" in s:
            tracker.restore_state(s["lineage"])


class StreamPipeline(TickLoop):
    def __init__(
        self,
        cfg: Optional[IngestConfig] = None,
        source: Optional[Source] = None,
        filter_stage: Optional[FilterStage] = None,
        transform: Optional[TransformStage] = None,
        buffer_stage: Optional[BufferControlStage] = None,
        consumer=None,
        sink=None,
        uncontrolled: bool = False,
        metrics: Optional[MetricsHub] = None,
        spill_dir: str = "/tmp/repro_spill",
        stages: Sequence = (),
    ):
        super().__init__(cfg, source, filter_stage, transform, consumer,
                         sink, metrics, stages)
        # explicit None check: an empty BufferControlStage is falsy
        # (__len__ == 0), so `or` would silently discard the caller's
        # stage — and with it the builder's controller and spill_dir
        self.buffer_stage = BufferControlStage(
            cfg=self.cfg, spill_dir=spill_dir) if buffer_stage is None \
            else buffer_stage
        self.uncontrolled = uncontrolled
        # cross-tick loop scalars; owned by the pipeline (not run()) so
        # checkpoint/resume (repro.resilience) can capture and restore
        # them — a resumed run continues the totals, not restarts them
        self.loop_state = new_loop_state(self.cfg)

    # ---- convenience accessors ----
    @property
    def controller(self):
        return self.buffer_stage.controller

    @property
    def buffer(self):
        return self.buffer_stage.buffer

    @property
    def system_delay_s(self) -> float:
        """alpha (Eq. 3): seconds of work queued at the consumer."""
        return self.consumer.delay_s

    def _step(self, recs, now: float, dt: float):
        intake(self.buffer_stage, self.loop_state, recs, now)
        tick = uncontrolled_tick if self.uncontrolled else controlled_tick
        tick(self.buffer_stage, self.transform, self.sink, self.consumer,
             self.metrics, self.loop_state, now, dt)

    def _report(self, wall_s: float) -> PipelineReport:
        st = self.loop_state
        return self.metrics.build_report(st["records"], st["instr"],
                                         st["raw"], st["crs"], wall_s)

    def state(self) -> dict:
        return {"loop": {**self.loop_state,
                         "crs": list(self.loop_state["crs"])},
                "buffer": self.buffer_stage.state(), **super().state()}

    def restore_state(self, s: dict) -> None:
        self.loop_state = dict(s["loop"])
        self.buffer_stage.restore_state(s["buffer"])
        super().restore_state(s)

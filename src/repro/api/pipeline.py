"""`StreamPipeline`: the paper's closed control loop over pluggable parts.

Each tick: Source -> FilterStage -> BufferControlStage; the controller
(Algorithm 2) decides push/hold/throttle/drain from the predictive
models; pushed buckets go through TransformStage (Algorithm 1 + graph
compression) into the Sink (Algorithm 3 GRAPHPUSH), and the Consumer
absorbs the instruction load and reports occupancy mu back to the
controller.  `uncontrolled=True` bypasses the controller — the paper's
meltdown baseline (Figs. 1-3, 7).

The loop itself is the only fixed part; every box is swappable via the
constructor (or `PipelineBuilder`).
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


def controlled_tick(buf: BufferControlStage, transform, sink, consumer,
                    hub: MetricsHub, state: dict, now: float, dt: float,
                    consume_dt: Optional[float] = None):
    """One controlled tick (Algorithm 2 steps 2-7) on one buffer.

    Shared by `StreamPipeline` (one buffer) and `ShardedPipeline` (one
    call per shard) so the loop semantics cannot drift between them.
    `consume_dt` is the slice of the tick this buffer may drain from
    the consumer — dt/n_shards when N buffers share one consumer.
    `state` carries the cross-tick scalars: last_beta_e/last_mu for the
    mu-model updates, and the records/instr/raw/crs totals.
    """
    cdt = dt if consume_dt is None else consume_dt
    tel = hub.telemetry
    pm = buf.perfmon
    aud = buf.controller.audit
    lineage = getattr(hub, "lineage", None)
    with tel.span("decide"):
        dec = buf.decide(len(buf) * 4.0, 0.0, now=now)

    if dec.action in ("push", "drain+push") and len(buf) >= 1:
        if dec.action == "drain+push" and buf.spill_depth:
            with tel.span("spill.drain"):
                buf.drain_spill()
            hub.emit("drain", now, depth=buf.spill_depth)
        batch = buf.take_batch()
        if batch:
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
            committed = out.get("committed", False)
            rho = out.get("rho", 1.0) if committed else 1.0
            cr, size, density = fetch_table_stats(tel, et)
            hub.emit("commit" if committed else "commit-failed", now,
                     instructions=n_instr, raw=raw_i, rho=rho, cr=cr,
                     dropped=out.get("dropped", 0),
                     probe_rounds=out.get("probe_rounds", 0),
                     pressure=out.get("pressure", 0.0),
                     refs=out.get("refs", 0),
                     dict_hit_rate=out.get("dict_hit_rate", 0.0))
            if out.get("pool_overflow"):
                hub.emit("pool_overflow", now, total=out["pool_overflow"])
            if out.get("degraded"):
                hub.emit("degraded", now, archived=out.get("archived", 0))
            if committed:
                # table pressure -> Algorithm-2 controller (back-pressure)
                pm.observe_pressure(out.get("pressure", 0.0),
                                    out.get("dropped", 0))
                if "dict_hit_rate" in out:
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
            state["instr"] += n_instr
            state["raw"] += raw_i
            state["crs"].append(cr)
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


class StreamPipeline:
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
        self.cfg = cfg or IngestConfig()
        self.source = source
        self.filter_stage = filter_stage or FilterStage()
        self.stages = list(stages)  # extra Stage-protocol record stages
        self.transform = transform or TransformStage(
            max_edges_per_batch=self.cfg.max_edges_per_batch)
        # explicit None check: an empty BufferControlStage is falsy
        # (__len__ == 0), so `or` would silently discard the caller's
        # stage — and with it the builder's controller and spill_dir
        self.buffer_stage = BufferControlStage(
            cfg=self.cfg, spill_dir=spill_dir) if buffer_stage is None \
            else buffer_stage
        self.consumer = consumer or SimulatedConsumer()
        self.sink = sink or GraphStoreSink(
            node_cap=self.cfg.store_nodes, edge_cap=self.cfg.store_edges)
        self.uncontrolled = uncontrolled
        self.metrics = metrics or MetricsHub()
        self.telemetry = self.metrics.telemetry
        # cross-tick loop scalars; owned by the pipeline (not run()) so
        # checkpoint/resume (repro.resilience) can capture and restore
        # them — a resumed run continues the totals, not restarts them
        self.loop_state: Optional[dict] = None

    # ---- convenience accessors ----
    @property
    def controller(self):
        return self.buffer_stage.controller

    @property
    def buffer(self):
        return self.buffer_stage.buffer

    @property
    def store(self):
        return self.sink.store

    @property
    def system_delay_s(self) -> float:
        """alpha (Eq. 3): seconds of work queued at the consumer."""
        return self.consumer.delay_s

    # ------------------------------------------------------------------
    def _transform_and_commit(self, records, now: float, dt: float):
        lineage = getattr(self.metrics, "lineage", None)
        tag = handed = None
        if lineage is not None:
            tag = lineage.open_batch(
                records, now, spilled=self.buffer_stage.last_take_spilled)
        et, n_instr, raw_instr = self.transform.encode(records)
        if tag is not None:
            handed = lineage.stage_commit(tag, self.sink)
        out = self.sink.commit(et, now=now)
        if tag is not None:
            lineage.after_commit(tag, out, now, handed=handed)
        mu = self.consumer.consume(n_instr, dt, now=now)
        committed = out.get("committed", False)
        rho = out.get("rho", 1.0) if committed else 1.0
        cr, size, density = fetch_table_stats(self.telemetry, et)
        self.metrics.emit("commit" if committed else "commit-failed", now,
                          instructions=n_instr, raw=raw_instr, rho=rho, cr=cr,
                          dropped=out.get("dropped", 0),
                          probe_rounds=out.get("probe_rounds", 0),
                          pressure=out.get("pressure", 0.0),
                          refs=out.get("refs", 0),
                          dict_hit_rate=out.get("dict_hit_rate", 0.0))
        return size, density, mu, rho, cr, n_instr, raw_instr

    # ------------------------------------------------------------------
    def run(self, source_ticks: Optional[Iterable] = None,
            max_ticks: int = 300) -> PipelineReport:
        if source_ticks is None:
            if self.source is None:
                raise ValueError("no source: pass source_ticks or set source")
            source_ticks = self.source.ticks()
        buf = self.buffer_stage
        pm = buf.perfmon
        hub = self.metrics
        t_start = time.time()
        state = self.loop_state
        if state is None:
            state = {"last_beta_e": self.cfg.beta_init, "last_mu": 0.0,
                     "records": 0, "instr": 0, "raw": 0, "crs": []}
            self.loop_state = state

        tel = self.telemetry
        for i, tick in enumerate(source_ticks):
            if i >= max_ticks:
                break
            now, dt = tick.t, 1.0
            ctx = TickContext(t=now, dt=dt, index=i)
            with tel.span("tick"):
                # ---- 1. filter (+ any extra record stages) ----
                with tel.span("filter"):
                    recs = self.filter_stage(tick.records, ctx)
                for stage in self.stages:
                    recs = stage(recs, ctx)
                state["records"] += len(recs)
                pm.observe_rate(now, len(recs))
                hub.emit("tick", now, raw=len(tick.records), kept=len(recs))
                # ---- 2. buffer ----
                buf.extend(recs)

                if self.uncontrolled:
                    # paper Figs. 1-3/7: push every tick, no control
                    if len(buf):
                        batch = buf.take_all()
                        (size, density, mu, rho, cr, ni,
                         ri) = self._transform_and_commit(batch, now, dt)
                        pm.observe_mu(mu)
                        state["instr"] += ni
                        state["raw"] += ri
                        state["crs"].append(cr)
                        hub.emit("push", now, records=len(batch))
                        hub.record(PerfSample(now, mu, rho, density,
                                              len(buf), size,
                                              *pm.velocity(), "push",
                                              buf.spill_depth, cr,
                                              self.consumer.delay_s))
                    maybe_retry_archive(self.sink, hub, now)
                    continue

                # ---- 3-7. controlled path ----
                controlled_tick(buf, self.transform, self.sink,
                                self.consumer, hub, state, now, dt)

        return hub.build_report(state["records"], state["instr"],
                                state["raw"], state["crs"],
                                time.time() - t_start)

    # ---- checkpoint surface (repro.resilience) -----------------------
    def state(self) -> dict:
        """Host-side resumable state: everything the checkpointer's
        array manifest does not cover (see resilience/checkpoint.py)."""
        s: dict = {
            "loop": None if self.loop_state is None else
                {**self.loop_state, "crs": list(self.loop_state["crs"])},
            "buffer": self.buffer_stage.state(),
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
        self.loop_state = None if s["loop"] is None else dict(s["loop"])
        self.buffer_stage.restore_state(s["buffer"])
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

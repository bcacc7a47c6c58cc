"""`PipelineBuilder` — the fluent facade over the composable API.

    pipe = (PipelineBuilder(IngestConfig(cpu_max=0.55))
            .with_source(BurstyTweetSource(seed=0))
            .with_keywords(["memo"])
            .simulated_consumer(speed=0.5)
            .spill_dir("/tmp/my_spill")
            .build())
    report = pipe.run(max_ticks=300)

`sharded(n)` switches `build()` to a `ShardedPipeline`; every part
not set explicitly gets the paper default.
"""
from __future__ import annotations

from typing import Callable, Iterable, Optional, Sequence, Union

from repro.api.consumers import MeasuredConsumer, SimulatedConsumer
from repro.api.metrics import MetricsHub, PipelineEvent
from repro.api.pipeline import StreamPipeline
from repro.api.sharded import ShardedPipeline
from repro.api.sinks import GraphStoreSink
from repro.api.stages import BufferControlStage, FilterStage, TransformStage
from repro.configs.paper_ingest import IngestConfig
from repro.core.buffer import BufferController
from repro.core.transform import MappingSpec

# placeholder in the stage list for a build-time-constructed SketchStage
_SKETCH_SLOT = object()
# placeholder for a build-time-constructed DictionaryStage (repro.compress)
_DICT_SLOT = object()


class PipelineBuilder:
    def __init__(self, cfg: Optional[IngestConfig] = None):
        self.cfg = cfg or IngestConfig()
        self._source = None
        self._filter: Optional[FilterStage] = None
        self._keywords: Sequence[str] = ()
        self._mapping: Optional[MappingSpec] = None
        self._transform: Optional[TransformStage] = None
        self._compress = True
        self._uncontrolled = False
        self._consumer = None
        self._sink = None
        self._controller: Optional[BufferController] = None
        self._spill_dir = "/tmp/repro_spill"
        self._n_shards = 1
        self._shard_key: Optional[Callable[[dict], str]] = None
        self._metrics: Optional[MetricsHub] = None
        self._hooks = []
        self._stages = []
        self._sketch_stage = None
        self._sketch_kw = {}
        self._query_sink_opts = None
        self._sketch_guided = False
        self._dict_stage = None
        self._compression_kw = None
        self._telemetry = None
        self._monitor = None
        self._monitor_kw = None
        self._lineage = None
        self._lineage_kw = None
        self._fault_plan = None
        self._fault_injector = None
        self._retry = None

    # ---- parts ----
    def with_source(self, source) -> "PipelineBuilder":
        self._source = source
        return self

    def with_filter(self, stage: FilterStage) -> "PipelineBuilder":
        self._filter = stage
        return self

    def with_keywords(self, keywords: Iterable[str]) -> "PipelineBuilder":
        self._keywords = list(keywords)
        return self

    def with_mapping(self, mapping: MappingSpec) -> "PipelineBuilder":
        self._mapping = mapping
        return self

    def with_transform(self, transform: TransformStage) -> "PipelineBuilder":
        self._transform = transform
        return self

    def with_stage(self, stage) -> "PipelineBuilder":
        """Append an extra Stage-protocol record stage (runs after the
        filter, before the buffer), e.g. a `repro.query.SketchStage`."""
        self._stages.append(stage)
        return self

    def with_sketch(self, sketch_stage=None, **kw) -> "PipelineBuilder":
        """Maintain an ingestion-time graph sketch (repro.query): adds
        a `SketchStage` after the filter.  When no stage is passed,
        one is created at build time inheriting the builder's mapping
        and the config's max_edges_per_batch (so the sketch observes
        exactly the edges the transform commits); retrieve it via
        `.sketch_stage` after build(), or keep the reference you pass."""
        self._sketch_stage = sketch_stage
        self._sketch_kw = dict(kw)
        self._stages.append(_SKETCH_SLOT)
        return self

    @property
    def sketch_stage(self):
        """The `SketchStage` added by `with_sketch` (after build())."""
        return self._sketch_stage

    def with_query_sink(self, **kw) -> "PipelineBuilder":
        """Wrap the sink in a `repro.query.QuerySink` at build time:
        commit-consistent sketch + live "sketch" MetricsHub events.
        Keyword args are forwarded to `QuerySink` (depth, width,
        answer_every, top_k, ...)."""
        self._query_sink_opts = dict(kw)
        return self

    def sketch_guided(self, flag: bool = True) -> "PipelineBuilder":
        """Sketch-guided control (ROADMAP): feed the QuerySink's live
        heavy-hitter/diversity signal back into each Algorithm-2
        controller via the MetricsHub "sketch" events.  Implies
        `with_query_sink()` when one wasn't configured."""
        self._sketch_guided = flag
        return self

    def with_compression(self, stage=None, **kw) -> "PipelineBuilder":
        """Ingestion-time dictionary compression (repro.compress, the
        paper's GraphZip layer): mines star/cascade patterns per bucket,
        rewrites recurring edges into `(pattern_id, bindings)` references
        against a device-resident dictionary, and commits them through
        the pattern-aware GRAPHPUSH path (`commit_compressed`).  When no
        stage is passed one is created at build time from the keyword
        args (capacity, star_min, hot_min, ttl); retrieve it
        via `.dictionary_stage` after build()."""
        self._dict_stage = stage
        self._compression_kw = dict(kw)
        self._stages.append(_DICT_SLOT)
        return self

    @property
    def dictionary_stage(self):
        """The `DictionaryStage` added by `with_compression` (after build())."""
        return self._dict_stage

    def with_consumer(self, consumer) -> "PipelineBuilder":
        self._consumer = consumer
        return self

    def simulated_consumer(self, speed: float = 1.0) -> "PipelineBuilder":
        self._consumer = SimulatedConsumer(speed=speed)
        return self

    def measured_consumer(self) -> "PipelineBuilder":
        """Use the real commit busy-fraction as mu (set at build time,
        once the sink's ingestor exists)."""
        self._consumer = "measured"
        return self

    def with_sink(self, sink) -> "PipelineBuilder":
        self._sink = sink
        return self

    def with_controller(self, controller: BufferController) -> "PipelineBuilder":
        self._controller = controller
        return self

    # ---- behaviour knobs ----
    def uncontrolled(self, flag: bool = True) -> "PipelineBuilder":
        self._uncontrolled = flag
        return self

    def compressed(self, flag: bool = True) -> "PipelineBuilder":
        self._compress = flag
        return self

    def spill_dir(self, path: str) -> "PipelineBuilder":
        self._spill_dir = path
        return self

    def sharded(self, n_shards: int,
                shard_key: Optional[Callable[[dict], str]] = None) -> "PipelineBuilder":
        if n_shards < 1:
            raise ValueError("n_shards must be >= 1")
        self._n_shards = n_shards
        self._shard_key = shard_key
        return self

    def with_metrics(self, hub: MetricsHub) -> "PipelineBuilder":
        self._metrics = hub
        return self

    def with_telemetry(self, registry=None) -> "PipelineBuilder":
        """Span telemetry + controller audit trail (repro.telemetry):
        threads one `TelemetryRegistry` through every layer — the
        MetricsHub (event counters + loop spans), the transform
        (map/dedup), the sink's ingestor (commit.upsert/wait/hooks),
        the sketch/dictionary stages, the snapshot maintainer, and an
        `AuditTrail` per controller (per-shard).  Pass a registry to
        share one across pipelines, or nothing to create one; read it
        back via `pipe.telemetry` / `pipe.metrics.telemetry`."""
        from repro.telemetry import TelemetryRegistry

        if registry is None or registry is True:
            registry = TelemetryRegistry()
        self._telemetry = registry
        return self

    def with_monitor(self, monitor=None, **kw) -> "PipelineBuilder":
        """Online health monitoring (repro.monitor): subscribe a
        `HealthMonitor` to the pipeline's MetricsHub and tap the
        telemetry registry for per-tick series — streaming anomaly
        detection (EWMA + Page–Hinkley `HealthEvent`s), SLO error
        budgets with burn-rate alerts, and controller decision-quality
        scoring.  Implies `with_telemetry()` (the monitor needs the
        span histograms and the audit trail).  Pass a configured
        monitor, or keyword args forwarded to `HealthMonitor` (series,
        slos, cpu_max, on_tick); read it back via `.health_monitor`
        (also set as `pipe.monitor` / `hub.monitor` after build)."""
        self._monitor = monitor
        self._monitor_kw = dict(kw)
        if self._telemetry is None:
            self.with_telemetry()
        return self

    @property
    def health_monitor(self):
        """The `HealthMonitor` wired by `with_monitor` (after build())."""
        return self._monitor

    def with_lineage(self, tracker=None, **kw) -> "PipelineBuilder":
        """Batch provenance + event-time watermarks (repro.lineage):
        tag every batch at the buffer with a monotone id + event-time
        envelope, follow it through spill/pool/archive to the
        queryable snapshot, and maintain the committed/queryable
        watermark pair plus per-path freshness histograms.  Pass a
        configured `LineageTracker`, or keyword args forwarded to it
        (sample_rate, dt, buffered_slack, ...); read it back via
        `.lineage_tracker` (also set as `pipe.lineage` /
        `hub.lineage` after build)."""
        self._lineage = tracker if tracker is not None \
            and tracker is not True else None
        self._lineage_kw = dict(kw)
        return self

    @property
    def lineage_tracker(self):
        """The `LineageTracker` wired by `with_lineage` (after build())."""
        return self._lineage

    def on_event(self, hook: Callable[[PipelineEvent], None]) -> "PipelineBuilder":
        self._hooks.append(hook)
        return self

    # ---- resilience (repro.resilience) ----
    def with_faults(self, plan) -> "PipelineBuilder":
        """Counter-deterministic fault injection: wire a `FaultPlan`
        (or a ready `FaultInjector`) as the sink ingestor's `fail_hook`
        at build time.  Read the injector back via `.fault_injector`
        (e.g. to inspect the attempt counter after a run)."""
        self._fault_plan = plan
        return self

    @property
    def fault_injector(self):
        """The `FaultInjector` wired by `with_faults` (after build())."""
        return self._fault_injector

    def with_retry(self, policy=None, *, max_archive: Optional[int] = None,
                   pool_cap: Optional[int] = None,
                   archive_dir: Optional[str] = None,
                   degrade_after: Optional[int] = None) -> "PipelineBuilder":
        """Backoff-governed commit retry: attach a `RetryPolicy`
        (default-constructed when none is given) to the sink's
        ingestor at build time.  This arms the per-tick auto-retry in
        the loop, the exponential-backoff gate, the degraded push mode,
        and — via the keyword overrides — the bounded archive
        (`max_archive` in-memory batches, disk spill beyond) and the
        pool hard cap."""
        from repro.resilience import RetryPolicy

        self._retry = (policy if policy is not None else RetryPolicy(), {
            "max_archive": max_archive, "pool_cap": pool_cap,
            "archive_dir": archive_dir, "degrade_after": degrade_after,
        })
        return self

    # ---- assembly ----
    def _resolve_stages(self):
        """Materialise the sketch slot with the builder's mapping/cap."""
        stages = []
        for st in self._stages:
            if st is _SKETCH_SLOT:
                if self._sketch_stage is None:
                    from repro.query.stage import SketchStage

                    kw = dict(self._sketch_kw)
                    kw.setdefault("mapping", self._mapping)
                    kw.setdefault("max_edges_per_batch",
                                  self.cfg.max_edges_per_batch)
                    self._sketch_stage = SketchStage(**kw)
                stages.append(self._sketch_stage)
            elif st is _DICT_SLOT:
                # materialised by build() before the pipeline exists
                if self._dict_stage is not None:
                    stages.append(self._dict_stage)
            else:
                stages.append(st)
        return stages

    def build(self) -> Union[StreamPipeline, ShardedPipeline]:
        filt = self._filter or FilterStage(self._keywords)
        transform = self._transform or TransformStage(
            mapping=self._mapping,
            max_edges_per_batch=self.cfg.max_edges_per_batch,
            compress=self._compress,
        )
        sink = self._sink or GraphStoreSink(
            node_cap=self.cfg.store_nodes, edge_cap=self.cfg.store_edges)
        consumer = self._consumer
        if consumer == "measured":
            if not isinstance(sink, GraphStoreSink):
                raise ValueError("measured_consumer() needs a GraphStoreSink")
            consumer = MeasuredConsumer(sink.ingestor)
        elif consumer is None:
            consumer = SimulatedConsumer()
        metrics = self._metrics or MetricsHub(telemetry=self._telemetry)
        if self._metrics is not None and self._telemetry is not None:
            metrics.telemetry = self._telemetry
        for h in self._hooks:
            metrics.subscribe(h)
        qs_opts = self._query_sink_opts
        if self._sketch_guided and qs_opts is None:
            qs_opts = {}  # sketch events need a QuerySink (build-local:
            # turning sketch_guided off again must not leave one behind)
        if qs_opts is not None:
            from repro.query.stage import QuerySink

            sink = QuerySink(sink, hub=metrics, **qs_opts)
        if self._compression_kw is not None:
            from repro.compress import CompressingTransform, DictionaryStage

            if self._dict_stage is None:
                self._dict_stage = DictionaryStage(**self._compression_kw)
            # rewrite happens in the transform (after Algorithm-1 encode);
            # the dictionary learns from SUCCESSFUL commits only, via the
            # ingestor's commit-hook fan-out (pooled/retried batches must
            # still admit their patterns exactly once).  `.ingestor`
            # passes through a QuerySink wrap.
            transform = CompressingTransform(transform, self._dict_stage)
            ingestor = getattr(sink, "ingestor", None)
            if ingestor is not None and hasattr(ingestor, "commit_hooks"):
                ingestor.commit_hooks.append(self._dict_stage.observe_commit)
        if self._fault_plan is not None or self._retry is not None:
            ingestor = getattr(sink, "ingestor", None)
            if ingestor is None:
                raise ValueError("with_faults()/with_retry() need a sink "
                                 "with a GraphIngestor underneath")
            if self._fault_plan is not None:
                from repro.resilience import FaultInjector, FaultPlan

                self._fault_injector = (
                    FaultInjector(self._fault_plan)
                    if isinstance(self._fault_plan, FaultPlan)
                    else self._fault_plan)
                ingestor.fail_hook = self._fault_injector
            if self._retry is not None:
                policy, overrides = self._retry
                ingestor.retry_policy = policy
                for name, val in overrides.items():
                    if val is not None:
                        setattr(ingestor, name, val)

        if self._n_shards > 1:
            if self._uncontrolled:
                raise ValueError("sharded pipelines are always controlled")
            if self._controller is not None:
                raise ValueError("with_controller() is single-shard only: "
                                 "each shard builds its own controller")
            pipe = ShardedPipeline(
                cfg=self.cfg,
                n_shards=self._n_shards,
                source=self._source,
                filter_stage=filt,
                transform=transform,
                consumer=consumer,
                sink=sink,
                spill_dir=self._spill_dir,
                shard_key=self._shard_key,
                metrics=metrics,
                stages=self._resolve_stages(),
            )
            controllers = [s.controller for s in pipe.shards]
        else:
            buffer_stage = BufferControlStage(
                controller=self._controller, cfg=self.cfg,
                spill_dir=self._spill_dir)
            pipe = StreamPipeline(
                cfg=self.cfg,
                source=self._source,
                filter_stage=filt,
                transform=transform,
                buffer_stage=buffer_stage,
                consumer=consumer,
                sink=sink,
                uncontrolled=self._uncontrolled,
                metrics=metrics,
                stages=self._resolve_stages(),
            )
            controllers = [buffer_stage.controller]
        if self._sketch_guided:
            # policy hook: live sketch events -> every controller's
            # diversity hint (sketch-guided control, see docs/API.md)
            def _guide(ev, _ctrls=controllers):
                if ev.kind == "sketch":
                    for c in _ctrls:
                        c.observe_sketch(ev.payload)

            metrics.subscribe(_guide)
        if self._telemetry is not None:
            self._wire_telemetry(pipe, transform, sink, controllers)
        if self._monitor is not None or self._monitor_kw is not None:
            from repro.monitor import HealthMonitor

            if self._monitor is None:
                self._monitor = HealthMonitor(**self._monitor_kw)
            self._monitor.bind(metrics, cfg=self.cfg)
            metrics.monitor = self._monitor
            pipe.monitor = self._monitor
        if self._lineage is not None or self._lineage_kw is not None:
            from repro.lineage import LineageTracker

            if self._lineage is None:
                self._lineage = LineageTracker(**(self._lineage_kw or {}))
            tracker = self._lineage
            metrics.lineage = tracker
            pipe.lineage = tracker
            # intake observation at every buffer stage, tag custody at
            # the ingestor, and the per-shard hubs `controlled_tick`
            # actually receives
            if isinstance(pipe, ShardedPipeline):
                for b in pipe.shards:
                    b.lineage = tracker
                for h in pipe._hubs:
                    h.lineage = tracker
            else:
                pipe.buffer_stage.lineage = tracker
            ingestor = getattr(sink, "ingestor", None)
            if ingestor is not None and hasattr(ingestor, "lineage"):
                ingestor.lineage = tracker
            # bind AFTER the monitor so the per-tick "watermark" event
            # lands in the tick row the monitor just opened
            tracker.bind(metrics)
        return pipe

    def _wire_telemetry(self, pipe, transform, sink, controllers):
        """Thread the registry through every instrumented layer."""
        from repro.telemetry import AuditTrail

        reg = self._telemetry
        if hasattr(transform, "telemetry"):
            transform.telemetry = reg  # CompressingTransform forwards
        for st in pipe.stages:  # SketchStage / DictionaryStage / customs
            if hasattr(st, "telemetry"):
                st.telemetry = reg
        # the sink chain: QuerySink wrapper, its maintainer, and the
        # GraphStoreSink's ingestor underneath (commit sub-spans)
        if hasattr(sink, "telemetry"):
            sink.telemetry = reg
        maintainer = getattr(sink, "maintainer", None)
        if maintainer is not None:
            maintainer.telemetry = reg
        ingestor = getattr(sink, "ingestor", None)
        if ingestor is not None and hasattr(ingestor, "telemetry"):
            ingestor.telemetry = reg
        # one audit trail per controller, tagged with its shard
        for si, c in enumerate(controllers):
            c.audit = AuditTrail(reg, shard=si)

    def run(self, max_ticks: int = 300):
        """Build and run in one call (source must be set)."""
        return self.build().run(max_ticks=max_ticks)

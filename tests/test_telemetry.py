"""Telemetry tests (PR 7): histogram bucket math, the null-span
zero-allocation discipline, MetricsHub emit/hook semantics, the
sharded counter-forwarding fix, the controller audit trail, and the
Chrome-trace/JSONL exporters."""
import json
import tracemalloc

import pytest

from repro.api import (
    GraphStoreSink,
    MetricsHub,
    PipelineBuilder,
)
from repro.configs.paper_ingest import IngestConfig
from repro.ingest.sources import BurstyTweetSource
from repro.telemetry import (
    INPUT_KEYS,
    NBUCKETS,
    NULL_REGISTRY,
    NULL_SPAN,
    Histogram,
    TelemetryRegistry,
    bucket_index,
    bucket_lower_ns,
    bucket_upper_ns,
    validate_chrome_trace,
)
from repro.workloads import run_scenario


# ---------------------------------------------------------------------------
# histogram bucket math (exact integer boundaries)
# ---------------------------------------------------------------------------


def test_bucket_index_exact_at_powers_of_two():
    assert bucket_index(0) == 0
    assert bucket_index(1) == 1
    for k in range(1, NBUCKETS - 2):
        # 2**k ns sits at the *bottom* of the half-open bucket k+1
        assert bucket_index(2 ** k) == k + 1
        assert bucket_index(2 ** k - 1) == k
        assert bucket_index(2 ** k + 1) == k + 1


def test_bucket_bounds_round_trip():
    for i in range(1, NBUCKETS - 1):
        assert bucket_index(bucket_lower_ns(i)) == i
        assert bucket_index(bucket_upper_ns(i) - 1) == i
    assert bucket_lower_ns(0) == 0 and bucket_upper_ns(0) == 1
    # durations past the last boundary clip into the final bucket
    assert bucket_index(1 << 100) == NBUCKETS - 1


def test_histogram_percentiles_conservative_and_clamped():
    h = Histogram()
    for _ in range(100):
        h.record_ns(1000)
    # all mass in one bucket: percentile reports its upper bound,
    # clamped to the observed max so it never exceeds real data
    assert h.percentile_ns(0.5) == 1000
    assert h.percentile_ns(0.99) == 1000
    assert h.count == 100 and h.sum_ns == 100_000 and h.max_ns == 1000
    st = h.stats()
    assert st["count"] == 100 and st["p95_ms"] == pytest.approx(1e-3)


def test_histogram_merge_adds_exactly():
    a, b = Histogram(), Histogram()
    a.record_ns(10)
    b.record_ns(10_000)
    b.record_ns(7)
    a.merge(b)
    assert a.count == 3
    assert a.sum_ns == 10_017
    assert a.max_ns == 10_000
    assert sum(a.counts) == 3


# ---------------------------------------------------------------------------
# span API: disabled path allocates nothing, enabled path records
# ---------------------------------------------------------------------------


def test_disabled_span_is_the_null_singleton():
    reg = TelemetryRegistry(enabled=False)
    assert reg.span("a") is NULL_SPAN
    assert reg.span("b") is NULL_SPAN
    assert NULL_REGISTRY.span("x") is NULL_SPAN
    with reg.span("a"):
        pass
    reg.observe("a", 1e-3)
    reg.count("a")
    assert reg.events == [] and reg.stage_names() == []
    assert reg.counters["a"] == 0  # count() is gated too


def test_disabled_path_zero_allocation_per_tick():
    """The telemetry-off hot path must not construct span objects:
    tracemalloc, filtered to spans.py, sees zero new allocations."""
    import repro.telemetry.spans as spans_mod

    reg = TelemetryRegistry(enabled=False)
    for _ in range(16):  # warm any lazy interpreter state
        with reg.span("tick"):
            reg.count("x")
    filt = (tracemalloc.Filter(True, spans_mod.__file__),)
    tracemalloc.start()
    before = tracemalloc.take_snapshot().filter_traces(filt)
    for _ in range(200):
        with reg.span("tick"):
            pass
        reg.observe("source.lag", 1e-6)
        reg.count("x")
    after = tracemalloc.take_snapshot().filter_traces(filt)
    tracemalloc.stop()
    grown = [d for d in after.compare_to(before, "lineno") if d.size_diff > 0]
    assert grown == [], f"disabled path allocated: {grown}"


def test_enabled_span_records_duration_and_event():
    reg = TelemetryRegistry()
    with reg.span("stage"):
        x = sum(range(1000))
    assert x is not None
    h = reg.hist("stage")
    assert h.count == 1 and h.sum_ns > 0
    assert len(reg.events) == 1
    name, shard, t0, t1 = reg.events[0]
    assert name == "stage" and shard is None and t1 >= t0


def test_timed_decorator_and_observe():
    reg = TelemetryRegistry()

    @reg.timed("fn")
    def work(n):
        return n * 2

    assert work(21) == 42
    assert reg.hist("fn").count == 1
    reg.observe("ext", 0.25)
    # an externally measured 0.25 s lands in the right log bucket
    assert reg.hist("ext").count == 1
    assert bucket_lower_ns(bucket_index(reg.hist("ext").sum_ns)) \
        <= int(0.25e9) < bucket_upper_ns(bucket_index(reg.hist("ext").sum_ns))


def test_child_registry_shares_spans_owns_counters():
    root = TelemetryRegistry()
    c0, c1 = root.child(0), root.child(1)
    with c0.span("tick"):
        pass
    with c1.span("tick"):
        pass
    c0.count("push")
    c1.count("push")
    c1.count("push")
    # spans land in the shared root store, shard-tagged
    assert root.hist("tick", shard=0).count == 1
    assert root.hist("tick", shard=1).count == 1
    assert root.aggregate("tick").count == 2
    assert root.shards() == [0, 1]
    # counters stay per-child (ShardedReport sums per-shard hubs)
    assert c0.counters["push"] == 1 and c1.counters["push"] == 2
    assert root.counters["push"] == 0
    # enable state mirrors through the root
    c0.enabled = False
    assert root.span("x") is NULL_SPAN and c1.span("x") is NULL_SPAN


def test_event_list_is_bounded():
    reg = TelemetryRegistry(max_events=5)
    for _ in range(9):
        with reg.span("s"):
            pass
    assert len(reg.events) == 5
    assert reg.events_dropped == 4
    assert reg.hist("s").count == 9  # histograms never drop


# ---------------------------------------------------------------------------
# MetricsHub emit semantics (satellite: pinned by tests)
# ---------------------------------------------------------------------------


def test_metrics_hub_counts_without_hooks():
    hub = MetricsHub()
    hub.emit("tick", 0.0)
    hub.emit("commit-failed", 1.0, error="x")
    hub.emit("commit-failed", 2.0, error="y")
    assert hub.counters["tick"] == 1
    assert hub.counters["commit-failed"] == 2
    assert hub.counters["never-emitted"] == 0


def test_metrics_hub_mid_run_subscriber_sees_subsequent_events():
    hub = MetricsHub()
    early, late = [], []
    hub.subscribe(early.append)
    hub.emit("tick", 0.0)
    hub.subscribe(late.append)  # joins mid-run
    hub.emit("push", 1.0, n=3)
    assert [e.kind for e in early] == ["tick", "push"]
    assert [e.kind for e in late] == ["push"]  # no replay of history
    assert late[0].payload == {"n": 3}
    assert hub.counters["tick"] == 1 and hub.counters["push"] == 1


def test_commit_failed_events_counted_end_to_end():
    """Injected commit failures surface as commit-failed counter hits."""
    cfg = IngestConfig()
    sink = GraphStoreSink(node_cap=1 << 10, edge_cap=1 << 11,
                          fail_hook=lambda: True)
    pipe = (PipelineBuilder(cfg)
            .with_source(BurstyTweetSource(seed=5))
            .with_sink(sink)
            .spill_dir("/tmp/repro_spill_tel_fail")
            .build())
    pipe.run(max_ticks=15)
    assert pipe.metrics.counters["commit-failed"] > 0
    assert pipe.metrics.counters["commit"] == 0


# ---------------------------------------------------------------------------
# sharded counter forwarding (satellite: the _forward fix)
# ---------------------------------------------------------------------------


def test_sharded_forward_routes_through_aggregate_emit():
    """Shard-loop events must land in the aggregate hub's counters
    (the pre-fix `_forward` invoked hooks directly and undercounted),
    and keep their shard tag for subscribers."""
    events = []
    pipe = (PipelineBuilder(IngestConfig())
            .with_source(BurstyTweetSource(seed=7))
            .sharded(2)
            .on_event(events.append)
            .spill_dir("/tmp/repro_spill_tel_fwd")
            .build())
    pipe.run(max_ticks=20)
    agg = pipe.metrics.counters
    assert agg["sample"] > 0 and agg["push"] > 0
    # aggregate counts == sum of the per-shard hub counts
    for kind in ("sample", "push", "commit"):
        assert agg[kind] == sum(h.counters[kind] for h in pipe._hubs), kind
    # shard tag preserved on the forwarded payload
    tags = {e.payload.get("shard") for e in events if e.kind == "sample"}
    assert tags == {0, 1}


# ---------------------------------------------------------------------------
# scenario-level acceptance: trace + audit + report breakdown
# ---------------------------------------------------------------------------

CORE_STAGES = ("tick", "filter", "decide", "transform.dedup", "commit.upsert")


@pytest.fixture(scope="module")
def flash_run(tmp_path_factory):
    d = tmp_path_factory.mktemp("telemetry")
    reg = TelemetryRegistry()
    rep = run_scenario(
        "flash_crowd", ticks=40, seed=0, shards=2,
        node_cap=1 << 12, edge_cap=1 << 14,
        spill_dir=str(d / "spill"),
        telemetry=reg,
        trace=str(d / "trace.json"),
        trace_jsonl=str(d / "spans.jsonl"),
    )
    return reg, rep, d


def test_run_scenario_emits_valid_chrome_trace(flash_run):
    reg, rep, d = flash_run
    ok, msg = validate_chrome_trace(str(d / "trace.json"),
                                    require_stages=CORE_STAGES)
    assert ok, msg
    trace = json.load(open(d / "trace.json"))
    evs = trace["traceEvents"]
    # per-shard timelines: spans on at least two distinct shard tracks
    span_tids = {e["tid"] for e in evs if e.get("ph") == "X"}
    assert len(span_tids) >= 2
    # audit decisions ride along as instant events with full args
    instants = [e for e in evs if e.get("ph") == "i"]
    assert instants and all("mu_pred" in e["args"] for e in instants)


def test_run_scenario_jsonl_sink_parses(flash_run):
    reg, rep, d = flash_run
    kinds = set()
    with open(d / "spans.jsonl") as f:
        for line in f:
            kinds.add(json.loads(line)["type"])
    assert {"span", "audit", "histogram", "counter"} <= kinds


def test_audit_trail_carries_full_input_vector(flash_run):
    reg, rep, d = flash_run
    assert rep.audit_decisions == len(reg.audit) > 0
    for rec in reg.audit:
        assert set(INPUT_KEYS) <= set(rec.inputs), rec
        assert rec.action in ("push", "hold", "throttle", "drain+push")
        if rec.action == "throttle":
            assert rec.reason in ("load", "pressure")
    # predicted-vs-realized: resolved records carry the measured outcome
    resolved = [r for r in reg.audit if r.mu_real is not None]
    assert len(resolved) >= len(reg.audit) - 2  # all but a trailing open one
    assert any(r.beta_e_real is not None and r.beta_e_real > 0
               for r in resolved)


def test_report_carries_stage_latency_breakdown(flash_run):
    reg, rep, d = flash_run
    assert rep.telemetry_enabled
    for stage in CORE_STAGES:
        assert stage in rep.stage_latency_ms, stage
        st = rep.stage_latency_ms[stage]
        assert st["count"] > 0 and st["p95_ms"] >= st["p50_ms"] >= 0
    assert "commit.wait" in rep.stage_latency_ms
    # the breakdown survives the JSON round-trip and the text summary
    assert json.dumps(rep.to_dict())
    assert "telemetry:" in rep.summary()


def test_run_scenario_telemetry_off_by_default():
    rep = run_scenario("steady_state", ticks=10,
                       node_cap=1 << 10, edge_cap=1 << 11,
                       spill_dir="/tmp/repro_spill_tel_off")
    assert not rep.telemetry_enabled
    assert rep.stage_latency_ms == {} and rep.audit_decisions == 0
    assert "telemetry:" not in rep.summary()


def test_compressed_run_records_dictionary_spans():
    reg = TelemetryRegistry()
    run_scenario("spam_storm", ticks=25, dict_compress=True,
                 node_cap=1 << 12, edge_cap=1 << 14,
                 spill_dir="/tmp/repro_spill_tel_dict", telemetry=reg)
    names = reg.stage_names()
    assert "dict.admit" in names
    assert any(n.startswith("rewrite.") for n in names)


def test_sketch_guided_run_records_sketch_spans():
    reg = TelemetryRegistry()
    run_scenario("flash_crowd", ticks=25, sketch_guided=True,
                 node_cap=1 << 12, edge_cap=1 << 14,
                 spill_dir="/tmp/repro_spill_tel_sketch", telemetry=reg)
    assert "sketch.absorb" in reg.stage_names()


def test_snapshot_maintainer_spans():
    from repro.graphstore.store import init_store
    from repro.query.snapshot import SnapshotMaintainer

    reg = TelemetryRegistry()
    m = SnapshotMaintainer()
    m.telemetry = reg
    m.snapshot(init_store(64, 64))
    assert "snapshot.rebuild" in reg.stage_names()


# ---------------------------------------------------------------------------
# spans in the profiler's trace, the device-to-host pulls, the edge cut
# ---------------------------------------------------------------------------


def _tweets(n, start=0):
    """`n` distinct tweets, four Fig. 6 edges each (one mention, one tag)."""
    return [{"id": f"t{i}", "user": f"u{i % 97}", "mentions": [f"u{i % 89}"],
             "hashtags": [f"h{i % 13}"]} for i in range(start, start + n)]


def _host_events(log_dir):
    import glob

    from jax.profiler import ProfileData

    (path,) = glob.glob(str(log_dir / "plugins" / "profile" / "*"
                            / "*.xplane.pb"))
    pd = ProfileData.from_file(path)
    return [e.name for plane in pd.planes if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events]


def test_enabled_spans_reach_the_profiler_trace(tmp_path):
    import jax

    from repro.api.stages import TransformStage

    reg = TelemetryRegistry()
    off = TelemetryRegistry(enabled=False)
    stage = TransformStage(max_edges_per_batch=256, telemetry=reg)
    with jax.profiler.trace(str(tmp_path)):
        stage.encode(_tweets(16))
        assert off.span("quiet") is NULL_SPAN
        with off.span("quiet"):
            pass
    names = _host_events(tmp_path)
    for span in ("transform.map", "transform.dedup", "transform.fetch"):
        assert f"repro.{span}" in names
    assert "repro.quiet" not in names
    # the registry's own record is unchanged by the annotation
    assert reg.hist("transform.map").count == 1


@pytest.mark.parametrize("uncontrolled", [False, True])
def test_pipeline_run_records_the_fetch_spans(uncontrolled):
    reg = TelemetryRegistry()
    pipe = (PipelineBuilder(IngestConfig(store_nodes=1 << 12,
                                         store_edges=1 << 13))
            .with_source(BurstyTweetSource(seed=3, mean_rate=40))
            .uncontrolled(uncontrolled)
            .spill_dir(f"/tmp/repro_spill_fetch{uncontrolled}")
            .with_telemetry(reg)
            .build())
    pipe.run(max_ticks=6)
    commits = reg.hist("commit.wait").count
    assert commits > 0
    for span in ("transform.fetch", "commit.fetch", "loop.fetch"):
        assert reg.hist(span).count == commits, span
    # `commit.total` duplicated commit.upsert + commit.wait and is gone
    assert "commit.total" not in reg.stage_names()


def test_transform_counts_the_edges_it_cuts():
    from repro.api.stages import TransformStage

    reg = TelemetryRegistry()
    stage = TransformStage(max_edges_per_batch=8_192, telemetry=reg)
    et, _, raw_instr = stage.encode(_tweets(2_100))  # 8,400 raw edges
    assert raw_instr == 3 * 8_400
    assert et.src.shape[0] == 8_192
    assert reg.counters["transform.edges_cut"] == 8_400 - 8_192
    stage.encode(_tweets(2_048, start=5_000))  # exactly one full table
    assert reg.counters["transform.edges_cut"] == 8_400 - 8_192


def test_transform_counts_the_lanes_it_pads():
    from repro.api.stages import TransformStage

    reg = TelemetryRegistry()
    stage = TransformStage(max_edges_per_batch=8_192, telemetry=reg)
    stage.encode(_tweets(2_048))  # one full table
    assert reg.counters["transform.lanes_padded"] == 0
    et, _, _ = stage.encode(_tweets(1_000, start=5_000))  # 4,000 edges
    assert et.src.shape[0] == 4_096
    assert reg.counters["transform.lanes_padded"] == 96
    stage.encode(_tweets(2_100, start=9_000))  # cut: no lane padded
    stage.encode(_tweets(5, start=20_000))  # 20 edges in 64 lanes
    assert reg.counters["transform.lanes_padded"] == 96 + 44

"""Composable-API tests: protocol round-trips, builder facade, the
push body's golden run, the commit outcome, the spans of one tick,
metrics hooks, and the sharded scale-out scenario."""
import json
import os

import numpy as np
import pytest

from repro.api import (
    Consumer,
    FilterStage,
    GraphStoreSink,
    MetricsHub,
    PipelineBuilder,
    ShardedPipeline,
    SimulatedConsumer,
    Sink,
    Source,
    Stage,
    StreamPipeline,
    TransformStage,
)
from repro.api.consumers import MeasuredConsumer
from repro.configs.paper_ingest import IngestConfig
from repro.core.ingestor import GraphIngestor
from repro.graphstore.store import init_store
from repro.ingest.sources import BurstyTweetSource, FileReplaySource, StreamTick


# ---------------------------------------------------------------------------
# protocol round-trips
# ---------------------------------------------------------------------------


def test_builtin_parts_satisfy_protocols(tmp_path):
    p = tmp_path / "x.jsonl"
    p.write_text('{"id": "t1", "user": "u1"}\n')
    assert isinstance(BurstyTweetSource(), Source)
    assert isinstance(FileReplaySource(str(p)), Source)
    assert isinstance(FilterStage(), Stage)
    assert isinstance(SimulatedConsumer(), Consumer)
    assert isinstance(MeasuredConsumer(GraphIngestor(init_store(64, 64))), Consumer)
    assert isinstance(GraphStoreSink(node_cap=64, edge_cap=64), Sink)


class ListSource:
    """Custom Source: replays a fixed list of ticks."""

    def __init__(self, ticks_):
        self._ticks = ticks_

    def ticks(self):
        return iter(self._ticks)


class CountingSink:
    """Custom Sink: counts commits, never touches a store."""

    def __init__(self):
        self.commits = 0

    def commit(self, et, now=None):
        self.commits += 1
        return {"committed": True, "rho": 1.0, "dropped": 0,
                "probe_rounds": 0, "pressure": 0.0, "refs": 0,
                "dict_hit_rate": None}


class FlatConsumer:
    """Custom Consumer: constant occupancy."""

    def __init__(self, mu=0.2):
        self.mu = mu
        self.calls = 0

    def consume(self, instructions, dt, now=None):
        self.calls += 1
        return self.mu

    @property
    def delay_s(self):
        return 0.0


def _toy_ticks(n=8, per=6):
    return [
        StreamTick(float(t + 1), [
            {"id": f"t{t}_{j}", "user": f"u{j % 3}",
             "hashtags": [f"h{j % 2}"], "mentions": []}
            for j in range(per)
        ])
        for t in range(n)
    ]


def test_custom_source_sink_consumer_roundtrip():
    src = ListSource(_toy_ticks())
    sink = CountingSink()
    consumer = FlatConsumer()
    assert isinstance(src, Source) and isinstance(sink, Sink)
    assert isinstance(consumer, Consumer)
    pipe = StreamPipeline(IngestConfig(), source=src, sink=sink,
                          consumer=consumer, uncontrolled=True,
                          spill_dir="/tmp/repro_spill_api_rt")
    rep = pipe.run(max_ticks=8)
    assert sink.commits == 8
    assert consumer.calls == 8
    assert rep.total_records == 8 * 6
    assert (rep.samples["mu"] == 0.2).all()


# ---------------------------------------------------------------------------
# the push body: a golden run, the commit outcome, the spans of one tick
# ---------------------------------------------------------------------------

_GOLDEN = os.path.join(os.path.dirname(__file__), "data", "push_golden.json")


@pytest.mark.parametrize("uncontrolled", [False, True])
def test_wrapper_matches_builder_pipeline(uncontrolled):
    """Golden run of the one push body: 50 ticks of a bursty stream give
    the totals, actions and mu/delay traces recorded before the
    uncontrolled and controlled pushes were folded into one function."""
    want = json.load(open(_GOLDEN))[
        "uncontrolled" if uncontrolled else "controlled"]
    pipe = (PipelineBuilder(IngestConfig())
            .with_source(BurstyTweetSource(seed=9, mean_rate=60,
                                           burst_multiplier=5.0))
            .uncontrolled(uncontrolled)
            .spill_dir(f"/tmp/repro_spill_par_b{uncontrolled}")
            .build())
    rep = pipe.run(max_ticks=50)
    assert rep.total_records == want["total_records"]
    assert rep.total_instructions == want["total_instructions"]
    assert rep.actions == want["actions"]
    np.testing.assert_array_equal(rep.samples["mu"], want["mu"])
    np.testing.assert_array_equal(rep.samples["delay_s"], want["delay_s"])


def _tiny_records(t=0, n=6):
    return [{"id": f"t{t}_{j}", "user": f"u{j % 3}",
             "hashtags": [f"h{j % 2}"], "mentions": [f"u{(j + 1) % 4}"]}
            for j in range(n)]


_OUTCOME_KEYS = {"committed", "stats", "busy_s", "rho", "instructions",
                 "dropped", "probe_rounds", "pressure", "refs",
                 "dict_hit_rate"}


@pytest.mark.parametrize("kind", ["raw", "compressed", "failed"])
def test_commit_outcome_keys(kind):
    """`GraphIngestor.push` returns one outcome shape on every commit
    that lands, raw or rewritten by the dictionary; a failed commit
    keeps the failure keys."""
    from repro.compress import CompressingTransform, DictionaryStage
    from repro.resilience import RetryPolicy

    transform = TransformStage(max_edges_per_batch=64)
    if kind == "compressed":
        transform = CompressingTransform(transform, DictionaryStage())
    et, _, _ = transform.encode(_tiny_records())
    ing = GraphIngestor(init_store(256, 512),
                        fail_hook=lambda: kind == "failed",
                        retry_policy=RetryPolicy(), degrade_after=1)
    out = ing.push(et, now=1.0)
    if kind == "failed":
        assert set(out) == {"committed", "retry_in_s", "degraded",
                            "archived"}
        assert not out["committed"] and out["archived"] == 1
        return
    assert set(out) == _OUTCOME_KEYS
    assert out["committed"] and out["instructions"] > 0
    if kind == "raw":
        assert out["refs"] == 0 and out["dict_hit_rate"] is None
    else:
        assert isinstance(out["refs"], int)
        assert isinstance(out["dict_hit_rate"], float)


# the spans one tick writes, in the order they close: the benchmark's
# span readers depend on these names and on their nesting under `tick`
_TICK_SPANS = {
    "uncontrolled": [
        "filter", "transform.map", "transform.dedup", "transform.fetch",
        "commit.upsert", "commit.wait", "commit.fetch", "commit.hooks",
        "consume", "loop.fetch", "tick"],
    "controlled": [
        "filter", "sketch.update", "decide", "transform.map",
        "transform.dedup", "transform.fetch", "rewrite.table",
        "transform.fetch", "commit.upsert", "commit.wait", "commit.fetch",
        "dict.admit", "commit.hooks", "consume", "loop.fetch", "tick"],
}


@pytest.mark.parametrize("mode", ["uncontrolled", "controlled"])
def test_one_tick_writes_the_benchmark_spans(mode, tmp_path):
    b = (PipelineBuilder(IngestConfig(store_nodes=256, store_edges=512,
                                      max_edges_per_batch=64))
         .measured_consumer().spill_dir(str(tmp_path)).with_telemetry())
    if mode == "uncontrolled":
        b.uncontrolled()
    else:
        b.with_sketch().with_compression()
    pipe = b.build()
    pipe.run(source_ticks=[StreamTick(1.0, _tiny_records())], max_ticks=1)
    assert [e[0] for e in pipe.telemetry.events] == _TICK_SPANS[mode]
    assert pipe.metrics.counters["commit"] == 1


# ---------------------------------------------------------------------------
# metrics / event hooks
# ---------------------------------------------------------------------------


def test_metrics_hub_hooks_see_loop_events():
    events = []
    pipe = (PipelineBuilder(IngestConfig())
            .with_source(BurstyTweetSource(seed=1))
            .on_event(events.append)
            .spill_dir("/tmp/repro_spill_api_hooks")
            .build())
    rep = pipe.run(max_ticks=30)
    kinds = {e.kind for e in events}
    assert "tick" in kinds and "sample" in kinds
    assert sum(e.kind == "tick" for e in events) == 30
    assert sum(e.kind == "sample" for e in events) == len(rep.actions)
    assert pipe.metrics.counters["push"] == rep.actions.count("push") + \
        rep.actions.count("drain+push")


# ---------------------------------------------------------------------------
# sharded scale-out
# ---------------------------------------------------------------------------


def test_sharded_pipeline_quickstart_scenario():
    """The quickstart scenario end-to-end on >= 2 shards: every shard
    buffer stays bounded by its controller and the shared store fills."""
    cfg = IngestConfig(cpu_max=0.55)
    pipe = (PipelineBuilder(cfg)
            .with_source(BurstyTweetSource(seed=42, mean_rate=60,
                                           burst_multiplier=5.0))
            .sharded(2)
            .spill_dir("/tmp/repro_spill_api_shard2")
            .build())
    assert isinstance(pipe, ShardedPipeline)
    rep = pipe.run(max_ticks=80)
    assert len(rep.shards) == 2
    # every record landed in exactly one shard
    assert sum(r.total_records for r in rep.shards) == rep.total_records
    assert rep.total_records > 0
    # all shard buffers bounded by the controller
    for hwm in rep.max_buffered:
        assert hwm <= cfg.beta_max
    for sr in rep.shards:
        assert set(sr.actions) <= {"push", "hold", "throttle", "drain+push"}
        assert (sr.samples["mu"] <= 1.0).all()
    # shared store got the union of shard commits
    assert int(pipe.store.n_nodes) > 0
    assert int(pipe.store.n_edges) > 0


def test_sharded_partition_is_deterministic_by_user():
    """Same user always routes to the same shard (graph locality)."""
    pipe = ShardedPipeline(IngestConfig(), n_shards=4,
                           spill_dir="/tmp/repro_spill_api_shard4")
    recs = [{"id": f"t{i}", "user": f"u{i % 7}"} for i in range(70)]
    parts_a = pipe._partition(recs)
    parts_b = pipe._partition(recs)
    for a, b in zip(parts_a, parts_b):
        assert a == b
    for part in parts_a:
        assert len({r["user"] for r in part} & {
            r["user"] for other in parts_a for r in other if other is not part
        }) == 0


# ---------------------------------------------------------------------------
# replay source: fractional-rate carry
# ---------------------------------------------------------------------------


def test_file_replay_fractional_rate_no_drift(tmp_path):
    """rate*dt = 4.9 must deliver ~4.9 records/tick on average, not 4."""
    path = tmp_path / "replay.jsonl"
    path.write_text("".join(f'{{"id": "t{i}", "user": "u{i}"}}\n'
                            for i in range(490)))
    src = FileReplaySource(str(path), rate_multiplier=1.0, natural_rate=4.9)
    counts = [len(t.records) for t in src.ticks()]
    # every record delivered, and per-tick counts hit both floor and ceil
    assert sum(counts) == 490
    mean = sum(counts[:-1]) / max(len(counts) - 1, 1)
    assert abs(mean - 4.9) < 0.2
    assert 5 in counts  # the carry must produce ceil ticks sometimes


def test_file_replay_sub_unit_rate(tmp_path):
    """rate*dt < 1 used to floor to zero records forever (and then
    dump the whole file as one EOF burst)."""
    path = tmp_path / "slow.jsonl"
    path.write_text("".join(f'{{"id": "t{i}", "user": "u{i}"}}\n'
                            for i in range(10)))
    src = FileReplaySource(str(path), rate_multiplier=1.0, natural_rate=0.5)
    counts = [len(t.records) for t in src.ticks()]
    assert sum(counts) == 10
    assert max(counts) == 1  # never more than ceil(0.5) per tick
    assert len(counts) == 20  # tail drains at the programmed rate


def test_sharded_consumer_capacity_is_shared_not_multiplied():
    """N shards draining one consumer must split each tick's capacity
    (dt/N each), not each take a full dt — otherwise the shared
    consumer silently becomes N consumers and never saturates."""

    class ProbeConsumer:
        def __init__(self):
            self.dts = []

        def consume(self, instructions, dt, now=None):
            self.dts.append(dt)
            return 0.1

        @property
        def delay_s(self):
            return 0.0

    probe = ProbeConsumer()
    pipe = ShardedPipeline(IngestConfig(), n_shards=2, consumer=probe,
                           sink=CountingSink(),
                           spill_dir="/tmp/repro_spill_api_dt")
    pipe.run(iter(_toy_ticks(n=6, per=8)), max_ticks=6)
    assert probe.dts  # every shard consumed every tick
    assert all(dt == 0.5 for dt in probe.dts)
    assert len(probe.dts) == 6 * 2


def test_sharded_events_forward_to_subscribers_with_shard_tag():
    events = []
    pipe = (PipelineBuilder(IngestConfig())
            .with_source(BurstyTweetSource(seed=2))
            .sharded(2)
            .on_event(events.append)
            .spill_dir("/tmp/repro_spill_api_shard_ev")
            .build())
    pipe.run(max_ticks=20)
    kinds = {e.kind for e in events}
    assert "sample" in kinds and "push" in kinds  # shard loop events arrive
    shard_tags = {e.payload.get("shard") for e in events if e.kind == "sample"}
    assert shard_tags == {0, 1}

"""`ShardedPipeline` — the first scale-out scenario.

Hash-partitions the filtered record stream by user across N shards,
each with its own adaptive buffer + Algorithm 2 controller (own spill
store, own PerfMon), all feeding one shared Sink/Consumer — the
paper's bounded DBMS ingestion pool fronted by parallel collectors.
Because the consumer is shared, every shard's controller observes the
*aggregate* occupancy mu and they collectively back off under load:
the control law needs no modification to go multi-collector.
"""
from __future__ import annotations

import dataclasses
import zlib
from typing import Callable, List, Optional, Sequence

import numpy as np

from repro.api.metrics import MetricsHub, PipelineEvent, PipelineReport
from repro.api.pipeline import (TickLoop, controlled_tick, intake,
                                new_loop_state)
from repro.api.protocols import Source
from repro.api.stages import BufferControlStage, FilterStage, TransformStage
from repro.configs.paper_ingest import IngestConfig


def default_shard_key(rec: dict) -> str:
    """Partition by user (graph locality: a user's edges co-locate)."""
    return str(rec.get("user") or rec.get("author") or rec.get("id") or "")


@dataclasses.dataclass
class ShardedReport:
    shards: List[PipelineReport]
    total_records: int
    total_instructions: int
    raw_instructions: int
    max_buffered: List[int]  # per-shard buffer high-water mark
    spill_events: int
    drain_events: int
    wall_s: float

    @property
    def mean_compression(self) -> float:
        crs = np.concatenate([r.compression_ratios for r in self.shards]) \
            if self.shards else np.asarray([])
        return float(crs.mean()) if crs.size else 1.0

    def mu_arrays(self) -> List[np.ndarray]:
        return [r.samples["mu"] for r in self.shards]


class ShardedPipeline(TickLoop):
    def __init__(
        self,
        cfg: Optional[IngestConfig] = None,
        n_shards: int = 2,
        source: Optional[Source] = None,
        filter_stage: Optional[FilterStage] = None,
        transform: Optional[TransformStage] = None,
        consumer=None,
        sink=None,
        spill_dir: str = "/tmp/repro_spill_shard",
        shard_key: Optional[Callable[[dict], str]] = None,
        metrics: Optional[MetricsHub] = None,
        stages: Sequence = (),
    ):
        if n_shards < 1:
            raise ValueError("n_shards must be >= 1")
        super().__init__(cfg, source, filter_stage, transform, consumer,
                         sink, metrics, stages)
        self.n_shards = n_shards
        self.shard_key = shard_key or default_shard_key
        self.shards = [
            BufferControlStage(cfg=self.cfg, spill_dir=f"{spill_dir}/shard{i}")
            for i in range(n_shards)
        ]
        # per-shard hubs: own counters (ShardedReport sums them), but
        # spans land in the aggregate registry tagged with the shard
        self._hubs = [MetricsHub(telemetry=self.telemetry.child(i))
                      for i in range(n_shards)]
        # forward every shard event to the caller's hub, tagged with the
        # shard index, so on_event() subscribers see the whole fleet
        for si, hub in enumerate(self._hubs):
            hub.subscribe(lambda ev, si=si: self._forward(ev, si))
        # per-shard cross-tick loop scalars; pipeline-owned so
        # checkpoint/resume (repro.resilience) can capture/restore them
        self.loop_states = [new_loop_state(self.cfg) for _ in range(n_shards)]

    def _forward(self, ev: PipelineEvent, shard: int):
        # route through emit (not the hooks directly) so the aggregate
        # hub's counters see shard-level spill/drain/commit events too;
        # subscribers keep receiving the shard-tagged payload
        self.metrics.emit(ev.kind, ev.t, **{**ev.payload, "shard": shard})

    def _partition(self, records: List[dict]) -> List[List[dict]]:
        parts: List[List[dict]] = [[] for _ in range(self.n_shards)]
        for r in records:
            h = zlib.crc32(self.shard_key(r).encode("utf-8"))
            parts[h % self.n_shards].append(r)
        return parts

    def _step(self, recs, now: float, dt: float):
        """Partition the tick, then run the exact single-shard loop body
        (`controlled_tick`) on each shard, with its slice of the shared
        consumer's capacity (dt/N, so N shards together drain one
        consumer-tick, not N)."""
        with self.telemetry.span("partition"):
            parts = self._partition(recs)
        for si, part in enumerate(parts):
            buf, state = self.shards[si], self.loop_states[si]
            intake(buf, state, part, now)
            with self.telemetry.span("shard.tick", shard=si):
                controlled_tick(buf, self.transform, self.sink, self.consumer,
                                self._hubs[si], state, now, dt,
                                consume_dt=dt / self.n_shards)

    def _report(self, wall_s: float) -> ShardedReport:
        states = self.loop_states
        reports = [
            hub.build_report(
                total_records=st["records"],
                total_instructions=st["instr"],
                raw_instructions=st["raw"],
                compression_ratios=st["crs"],
                wall_s=wall_s,
            )
            for hub, st in zip(self._hubs, states)
        ]
        return ShardedReport(
            shards=reports,
            # the partition is total: per-shard record counts sum to the
            # filtered stream (and survive checkpoint/resume)
            total_records=sum(st["records"] for st in states),
            total_instructions=sum(st["instr"] for st in states),
            raw_instructions=sum(st["raw"] for st in states),
            max_buffered=[b.max_buffered for b in self.shards],
            spill_events=sum(h.counters["spill"] for h in self._hubs),
            drain_events=sum(h.counters["drain"] for h in self._hubs),
            wall_s=wall_s,
        )

    # ---- checkpoint surface (repro.resilience) -----------------------
    def state(self) -> dict:
        return {"loops": [{**st, "crs": list(st["crs"])}
                          for st in self.loop_states],
                "shards": [b.state() for b in self.shards],
                "hubs": [h.state() for h in self._hubs], **super().state()}

    def restore_state(self, s: dict) -> None:
        self.loop_states = [dict(st) for st in s["loops"]]
        for b, b_s in zip(self.shards, s["shards"]):
            b.restore_state(b_s)
        for h, h_s in zip(self._hubs, s["hubs"]):
            h.restore_state(h_s)
        super().restore_state(s)

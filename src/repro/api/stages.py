"""Stage implementations: the swappable steps of the seven-step loop.

  FilterStage        — two-stage filtering (§II-A): source-API keyword
                       filter + analysis filter.
  TransformStage     — model transformation (Algorithm 1 CREATEEDGE)
                       plus ingestion-time graph compression; owns the
                       instruction accounting for both paths.
  BufferControlStage — the adaptive buffer + Algorithm 2 controller
                       state (buffer list, spill store, decisions).
"""
from __future__ import annotations

from typing import Callable, List, Optional, Sequence, Tuple

import numpy as np

from repro.api.protocols import TickContext
from repro.configs.paper_ingest import IngestConfig
from repro.core.buffer import BufferController, ControllerDecision
from repro.core.edge_table import EdgeTable, from_raw_batch
from repro.core.transform import MappingSpec, create_edges, tweet_mapping
from repro.ingest.filter import analysis_filter, api_keyword_filter, apply_filters


class FilterStage:
    """§II-A two-stage filter as one record stage."""

    name = "filter"

    def __init__(self, keywords: Sequence[str] = (),
                 stage2: Callable[[dict], bool] = analysis_filter):
        self.stage1 = api_keyword_filter(list(keywords))
        self.stage2 = stage2

    def __call__(self, records: List[dict], ctx: Optional[TickContext] = None) -> List[dict]:
        return apply_filters(records, self.stage1, self.stage2)


class TransformStage:
    """Records -> compressed device edge table + instruction counts.

    `compress=False` keeps the compressed table for the store (the
    store only speaks edge tables) but accounts the ingestion load at
    the raw instruction stream — the paper's uncompressed baseline.
    """

    name = "transform"

    def __init__(self, mapping: Optional[MappingSpec] = None,
                 max_edges_per_batch: int = 8_192, compress: bool = True,
                 telemetry=None):
        from repro.telemetry.spans import NULL_REGISTRY

        self.mapping = mapping or tweet_mapping()
        self.max_edges_per_batch = max_edges_per_batch
        self.compress = compress
        self.telemetry = telemetry or NULL_REGISTRY

    def encode(self, records: List[dict]) -> Tuple[EdgeTable, int, int]:
        tel = self.telemetry
        with tel.span("transform.map"):
            raw = create_edges(records, self.mapping)
        cap = max(64, 1 << int(np.ceil(np.log2(max(raw.n_edges, 1)))))
        cap = min(cap, self.max_edges_per_batch)
        if raw.n_edges > cap:
            # `from_raw_batch` keeps the first `cap` raw edges of the tick
            tel.count("transform.edges_cut", raw.n_edges - cap)
        # lanes of the table that hold no raw edge: how far below full
        # the tables run
        tel.count("transform.lanes_padded", cap - min(raw.n_edges, cap))
        with tel.span("transform.dedup"):
            et = from_raw_batch(raw, cap)
        raw_instr = 3 * raw.n_edges
        if not self.compress:
            # uncompressed baseline: ingestion load = raw instructions
            n_instr = raw_instr
        else:
            with tel.span("transform.fetch"):
                n_instr = int(et.n_nodes) + int(et.n_edges)
        return et, n_instr, raw_instr


class BufferControlStage:
    """The adaptive buffer (Algorithm 2) as a pipeline stage: owns the
    in-memory record buffer, the spill store, and the controller."""

    name = "buffer"

    def __init__(self, controller: Optional[BufferController] = None,
                 cfg: Optional[IngestConfig] = None,
                 spill_dir: str = "/tmp/repro_spill"):
        self.controller = controller or BufferController(cfg or IngestConfig(),
                                                         spill_dir=spill_dir)
        self.buffer: List[dict] = []
        self.max_buffered = 0  # high-water mark (sharding bound checks)
        # provenance (repro.lineage): per-record came-back-from-spill
        # flags parallel to `buffer`, the count of records currently
        # detoured to disk, and whether the last take touched spill
        self._spill_flags: List[bool] = []
        self.spilled_records = 0
        self.last_take_spilled = False
        self.lineage = None  # LineageTracker (set by builder wiring)

    # ---- buffer plumbing ----
    def extend(self, records: List[dict]):
        if self.lineage is not None:
            self.lineage.observe_intake(records)
        self.buffer.extend(records)
        self._spill_flags.extend([False] * len(records))
        self.max_buffered = max(self.max_buffered, len(self.buffer))

    def take_batch(self) -> List[dict]:
        """Pop up to beta records (the controller's current bucket)."""
        batch = self.buffer[: self.controller.beta]
        self.buffer = self.buffer[self.controller.beta :]
        taken = self._spill_flags[: len(batch)]
        self._spill_flags = self._spill_flags[len(batch):]
        self.last_take_spilled = any(taken)
        return batch

    def take_all(self) -> List[dict]:
        batch, self.buffer = self.buffer, []
        self.last_take_spilled = any(self._spill_flags)
        self._spill_flags = []
        return batch

    def spill_all(self) -> int:
        """Data throttling: flush the whole buffer to disk."""
        n = len(self.buffer)
        if self.buffer:
            self.controller.spill.flush(self.buffer)
            self.buffer = []
            self._spill_flags = []
            self.spilled_records += n
        return n

    def drain_spill(self):
        """Step 6: reload spilled data into the buffer."""
        drained = self.controller.spill.drain()
        self.spilled_records = max(0, self.spilled_records - len(drained))
        self.buffer.extend(drained)
        self._spill_flags.extend([True] * len(drained))
        self.max_buffered = max(self.max_buffered, len(self.buffer))

    # ---- checkpoint surface (repro.resilience) ----
    def state(self) -> dict:
        return {
            "buffer": list(self.buffer),
            "max_buffered": self.max_buffered,
            "controller": self.controller.state(),
            "spill_flags": list(self._spill_flags),
            "spilled_records": self.spilled_records,
        }

    def restore_state(self, s: dict) -> None:
        self.buffer = list(s["buffer"])
        self.max_buffered = int(s["max_buffered"])
        self.controller.restore_state(s["controller"])
        # .get: checkpoints written before lineage landed lack these
        self._spill_flags = list(s.get("spill_flags",
                                       [False] * len(self.buffer)))
        self.spilled_records = int(s.get("spilled_records", 0))

    # ---- controller passthrough ----
    def decide(self, size_est: float, density: float,
               now: Optional[float] = None) -> ControllerDecision:
        return self.controller.decide(size_est, density, now=now)

    @property
    def perfmon(self):
        return self.controller.perfmon

    @property
    def spill_depth(self) -> int:
        return self.controller.spill.depth

    def __len__(self) -> int:
        return len(self.buffer)

"""Ingestion-time sketch maintenance: the pipeline plug-ins.

Two complementary placements, both over `repro.api` protocols:

  * `SketchStage` — a `Stage` (records -> records pass-through) that
    maps each tick's filtered records through the same declarative
    `MappingSpec` the transform uses and absorbs the resulting edge
    table into its sketch.  It observes the stream at *filter time*,
    before the buffer/controller, so its answers are available live
    even while batches are held, spilled or throttled — and since
    every record passes here at most once (spill-drain re-enters the
    buffer, not the filter), sketch totals upper-bound store totals.
  * `QuerySink` — a `Sink` wrapper that updates its sketch only on
    *committed* edge tables, so its sketch is commit-consistent with
    the store; it can periodically publish live answers as `"sketch"`
    events on the `MetricsHub`.

Both expose the same numpy-friendly query surface: `degree`,
`edge_weight`, `heavy_hitters`, `error_bound`.
"""
from __future__ import annotations

from typing import Dict, List, Optional

import numpy as np

from repro.core.edge_table import from_raw_batch
from repro.core.transform import MappingSpec, create_edges, tweet_mapping
from repro.query.sketch import (
    GraphSketch,
    init_sketch,
    sketch_degree,
    sketch_edge_weight,
    sketch_error_bound,
    sketch_heavy_hitters,
    sketch_update,
)


def _slice_raw(raw, lo: int, hi: int):
    import dataclasses

    return dataclasses.replace(
        raw, src=raw.src[lo:hi], dst=raw.dst[lo:hi], etype=raw.etype[lo:hi],
        src_type=raw.src_type[lo:hi], dst_type=raw.dst_type[lo:hi])


class _SketchQueries:
    """Shared numpy-facing query surface over `self.sketch`."""

    sketch: GraphSketch

    def degree(self, keys, mode: str = "total") -> np.ndarray:
        import jax.numpy as jnp

        kd = self.sketch.hh_keys.dtype
        return np.asarray(sketch_degree(self.sketch, jnp.asarray(keys, kd),
                                        mode=mode))

    def edge_weight(self, src, dst) -> np.ndarray:
        import jax.numpy as jnp

        kd = self.sketch.hh_keys.dtype
        return np.asarray(sketch_edge_weight(
            self.sketch, jnp.asarray(src, kd), jnp.asarray(dst, kd)))

    def heavy_hitters(self, k: int = 10):
        hk, hc = sketch_heavy_hitters(self.sketch, k)
        return np.asarray(hk), np.asarray(hc)

    def error_bound(self) -> float:
        return sketch_error_bound(self.sketch)


class SketchStage(_SketchQueries):
    """Stage-protocol pass-through observer maintaining a graph sketch
    at filter time (see module docstring for placement semantics)."""

    name = "sketch"

    def __init__(self, sketch: Optional[GraphSketch] = None,
                 mapping: Optional[MappingSpec] = None,
                 depth: int = 4, width: int = 256, hh_slots: int = 64,
                 max_edges_per_batch: int = 8_192):
        from repro.telemetry.spans import NULL_REGISTRY

        self.sketch = sketch if sketch is not None else init_sketch(
            depth=depth, width=width, hh_slots=hh_slots)
        self.mapping = mapping or tweet_mapping()
        self.max_edges_per_batch = max_edges_per_batch
        self.ticks_seen = 0
        self.telemetry = NULL_REGISTRY

    def __call__(self, records: List[dict], ctx=None) -> List[dict]:
        if records:
            with self.telemetry.span("sketch.update"):
                raw = create_edges(records, self.mapping)
                # absorb in <=cap chunks: a burst tick larger than the
                # device batch must never silently truncate, or the
                # sketch-upper-bounds-the-store guarantee breaks
                for lo in range(0, raw.n_edges, self.max_edges_per_batch):
                    hi = min(lo + self.max_edges_per_batch, raw.n_edges)
                    cap = max(64, 1 << int(np.ceil(np.log2(hi - lo))))
                    et = from_raw_batch(_slice_raw(raw, lo, hi), cap)
                    self.sketch = sketch_update(self.sketch, et)
        self.ticks_seen += 1
        return records

    # ---- checkpoint surface (repro.resilience); the sketch itself
    # snapshots as array leaves, not here ----
    def state(self) -> dict:
        return {"ticks_seen": self.ticks_seen}

    def restore_state(self, s: dict) -> None:
        self.ticks_seen = int(s["ticks_seen"])


class QuerySink(_SketchQueries):
    """Sink wrapper: commit-consistent sketch + live `"sketch"` events
    + incrementally maintained exact CSR snapshot.

    Delegates `commit` to the wrapped sink and absorbs every edge
    table the store *actually* commits: when the wrapped sink exposes
    a `GraphIngestor` (duck-typed via `.ingestor.commit_hook`), the
    sketch hooks the ingestor's successful-commit callback — which
    also catches pooled batches drained by later pushes and archived
    batches replayed by `retry_archive`.  Otherwise it falls back to
    absorbing the pushed table when the commit reports success.
    Every `answer_every` commits, a `"sketch"` event with the current
    top-k heavy hitters is emitted on `hub` (when given).

    With `incremental=True` (default) a `SnapshotMaintainer` also
    observes every commit's `CommitDelta`, so `snapshot()` serves an
    exact CSR view by merging pending deltas instead of paying a full
    `build_snapshot` per query.  `exact_topk > 0` additionally puts
    the exact top-k degrees (from the maintained snapshot) on each
    live `"sketch"` event — query-while-ingesting without rebuilds.
    """

    def __init__(self, inner, sketch: Optional[GraphSketch] = None,
                 depth: int = 4, width: int = 256, hh_slots: int = 64,
                 hub=None, answer_every: int = 10, top_k: int = 5,
                 incremental: bool = True, exact_topk: int = 0):
        from repro.query.snapshot import SnapshotMaintainer
        from repro.telemetry.spans import NULL_REGISTRY

        self.telemetry = NULL_REGISTRY
        self.inner = inner
        self.sketch = sketch if sketch is not None else init_sketch(
            depth=depth, width=width, hh_slots=hh_slots)
        self.hub = hub
        self.answer_every = max(1, answer_every)
        self.top_k = top_k
        self.exact_topk = exact_topk
        self.commits = 0
        self._now = None
        self._hooked = False
        self.maintainer = SnapshotMaintainer() if incremental else None
        ingestor = getattr(inner, "ingestor", None)
        if ingestor is not None and hasattr(ingestor, "commit_hook"):
            ingestor.commit_hook = self._absorb
            self._hooked = True

    def snapshot(self):
        """Exact CSR snapshot of the committed store — incrementally
        maintained (delta merges; full rebuild only on overflow or
        dangling edges) when `incremental`, else a fresh build."""
        from repro.query.snapshot import build_snapshot

        if self.maintainer is None:
            return build_snapshot(self.store)
        return self.maintainer.snapshot(self.store)

    def _absorb(self, et, stats):
        # the maintainer must see the commit's delta BEFORE any
        # exact_topk emission below serves snapshot(), or the served
        # view lags the store by one commit (and the lag would be
        # misread as dangling edges, forcing a rebuild per query)
        if self.maintainer is not None:
            self.maintainer.absorb(et, stats)
        with self.telemetry.span("sketch.absorb"):
            self.sketch = sketch_update(self.sketch, et)
        self.commits += 1
        if self.hub is not None and self.commits % self.answer_every == 0:
            hk, hc = self.heavy_hitters(self.top_k)
            payload = dict(
                commits=self.commits,
                absorbed=int(self.sketch.n_updates),
                hh_keys=hk.tolist(), hh_counts=hc.tolist(),
                error_bound=self.error_bound(),
            )
            if self.exact_topk > 0 and self.maintainer is not None:
                from repro.query.engine import top_k_degree

                keys, degs = top_k_degree(self.snapshot(), self.exact_topk)
                payload["exact_keys"] = np.asarray(keys).tolist()
                payload["exact_degrees"] = np.asarray(degs).tolist()
            self.hub.emit(
                "sketch", self._now if self._now is not None else 0.0,
                **payload,
            )

    def commit(self, et, now: Optional[float] = None) -> Dict:
        self._now = now
        out = self.inner.commit(et, now=now)
        if not self._hooked and out.get("committed", False):
            self._absorb(et, out.get("stats"))
        return out

    # ---- checkpoint surface (repro.resilience) ----
    def state(self) -> Dict:
        s: Dict = {"commits": self.commits}
        if hasattr(self.inner, "state"):
            s["inner"] = self.inner.state()
        return s

    def restore_state(self, s: Dict) -> None:
        self.commits = int(s["commits"])
        self._now = None
        if self.maintainer is not None:
            # cheaper than checkpointing the CSR: force one full rebuild
            # (apply_delta is bit-exact vs build_snapshot, so the views
            # converge identically)
            self.maintainer.reset()
        if "inner" in s and hasattr(self.inner, "restore_state"):
            self.inner.restore_state(s["inner"])

    # ---- passthrough of the wrapped sink's surface ----
    def retry_archive(self, now: Optional[float] = None) -> int:
        self._now = now
        return self.inner.retry_archive(now)

    @property
    def store(self):
        return self.inner.store

    @property
    def ingestor(self):
        return self.inner.ingestor

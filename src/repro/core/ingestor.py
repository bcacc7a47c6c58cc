"""Graph Ingestor + Commit (Algorithm 3 GRAPHPUSH).

Bridges the pipeline to the graph store: converts compressed edge
tables into store commits, respecting a bounded ingestion pool
(the paper's bolt-connector pool), with commit-failure archiving and
retry.  The consumer-occupancy measurement lives here: mu = busy-time
of the ingest engine over the sampling window — the TPU-native stand-in
for the paper's Zabbix CPU-user-time (DESIGN.md §2).

Resilience posture (repro.resilience):
  * the archive is BOUNDED — past `max_archive` in-memory batches,
    failed commits spill to disk (pickled host pytrees) and refill
    FIFO as retries drain them, so a long outage cannot OOM the host;
  * the pool has a hard cap (`pool_cap`, default 4x `max_pool_size`):
    overflow batches divert to the archive instead of growing the
    deque without bound, counted in `pool_overflows`;
  * with a `RetryPolicy` attached, consecutive commit failures arm a
    capped-exponential-backoff gate (`next_retry_t`): `retry_archive`
    refuses to hot-loop while the gate is closed, and after
    `degrade_after` consecutive failures `push` enters DEGRADED mode —
    batches archive directly without hammering the dead store, while
    sketch/telemetry service upstream continues.  With no policy
    (the default) every legacy behavior is unchanged.
"""
from __future__ import annotations

import collections
import dataclasses
import os
import pickle
import tempfile
import time
from typing import Deque, List, Optional, Tuple

import jax
import numpy as np

from repro.core.edge_table import EdgeTable
from repro.graphstore.store import GraphStore, commit_compressed, ingest_step
from repro.telemetry.spans import NULL_REGISTRY


# `ingest_step`'s probe-work stats, fetched together once per commit
_ROUND_KEYS = ("node_rounds_run", "edge_rounds_run",
               "node_rounds_needed", "edge_rounds_needed")


@dataclasses.dataclass
class CommitRecord:
    t: float
    busy_s: float
    instructions: int
    new_nodes: int
    batch_nodes: int
    ok: bool
    # the probe budget the sweeps ran with: the Algorithm-2 controller's
    # table-pressure input (not the rounds they did work in; see below)
    probe_rounds: int = 0
    dropped: int = 0  # inserts lost to table pressure (probing exhausted)
    refs: int = 0  # dictionary pattern references applied (repro.compress)
    # probe work, summed over the node and the edge sweep: the rounds
    # their loops executed, and the rounds that placed their last lane
    rounds_run: int = 0
    rounds_needed: int = 0
    # lanes of a rewritten table's residual and references that held no
    # edge (repro.compress; 0 for a table that was not rewritten)
    lanes_idle: int = 0


def _to_host(et):
    """Edge-table pytree -> host numpy leaves (pickle/spill-safe)."""
    return jax.tree_util.tree_map(lambda x: np.asarray(jax.device_get(x)), et)


class GraphIngestor:
    def __init__(self, store: GraphStore, max_pool_size: int = 4, fail_hook=None,
                 occupancy_window: float = 10.0, retry_policy=None,
                 pool_cap: Optional[int] = None, max_archive: int = 128,
                 archive_dir: Optional[str] = None, degrade_after: int = 3):
        self.store = store
        self.max_pool_size = max_pool_size
        # hard admission ceiling: beyond it, batches divert to the archive
        self.pool_cap = pool_cap if pool_cap is not None else 4 * max_pool_size
        self.pool: Deque[EdgeTable] = collections.deque()
        self.archive: Deque[EdgeTable] = collections.deque()  # Alg. 3 line 18
        self.commits: List[CommitRecord] = []
        self.fail_hook = fail_hook  # fault injection (nullary, or a
        # repro.resilience.FaultInjector with `wants_now = True`)
        # observers of every SUCCESSFUL commit: hook(et, stats).  Push can
        # drain pooled batches and retry_archive replays old ones, so a
        # commit-consistent observer (e.g. repro.query.QuerySink) must
        # hook here rather than watch push() arguments.  `commit_hook`
        # is the single assignable slot (sketch maintenance);
        # `commit_hooks` fan out to any number of extra observers
        # (e.g. the incremental snapshot maintainer).
        self.commit_hook = None
        self.commit_hooks: List = []
        self.occupancy_window = occupancy_window
        self._busy: Deque[Tuple[float, float]] = collections.deque(maxlen=512)
        # span telemetry (repro.telemetry): commit milliseconds split
        # into upsert-dispatch / device-wait / stats-fetch / observer-hook
        # sub-spans (commit.upsert / .wait / .fetch / .hooks).
        # NULL_REGISTRY = disabled; PipelineBuilder.with_telemetry swaps
        # in the live registry.
        self.telemetry = NULL_REGISTRY
        # ---- resilience (repro.resilience; None policy = legacy) ----
        self.retry_policy = retry_policy
        self.max_archive = max_archive
        self.archive_dir = archive_dir
        self.degrade_after = degrade_after
        self._archive_spill: List[str] = []  # on-disk overflow, FIFO
        self._archive_n = 0  # monotone spill-file counter
        self.consecutive_failures = 0
        self.next_retry_t = float("-inf")  # backoff gate (simulated time)
        # accounting: archived_total == replayed + archive_depth must
        # hold at all times (the chaos harness's no-batch-lost invariant)
        self.archived_total = 0
        self.replayed = 0
        self.attempts = 0
        self.pool_overflows = 0
        # ---- provenance (repro.lineage; None tracker = zero cost) ----
        # `_lineage_next` is the tag the pipeline staged for the very
        # next push; `_pool_tags`/`_archive_tags` ride parallel to the
        # pool and the LOGICAL archive (memory + disk spill, FIFO) so
        # the spill-file format stays unchanged.  Tests that poke
        # batches straight into `pool`/`archive` never see any of this:
        # every tag op is guarded on the tracker and on deque depth.
        self.lineage = None
        self._lineage_next = None
        self._pool_tags: Deque = collections.deque()
        self._archive_tags: Deque = collections.deque()

    # ---- archive (bounded, disk-spilled past max_archive) -----------
    @property
    def archive_depth(self) -> int:
        """Failed batches awaiting replay, memory + disk."""
        return len(self.archive) + len(self._archive_spill)

    @property
    def degraded(self) -> bool:
        """Store considered down: policy attached and the consecutive-
        failure count passed `degrade_after`."""
        return (self.retry_policy is not None
                and self.consecutive_failures >= self.degrade_after)

    def _spill_path(self) -> str:
        if self.archive_dir is None:
            self.archive_dir = tempfile.mkdtemp(prefix="repro_archive_")
        os.makedirs(self.archive_dir, exist_ok=True)
        fn = os.path.join(self.archive_dir,
                          f"archive_{self._archive_n:08d}.pkl")
        self._archive_n += 1
        return fn

    def _archive_put(self, et, tag=None, now: Optional[float] = None,
                     degraded: bool = False) -> None:
        if self.lineage is not None and tag is not None:
            self._archive_tags.append(tag)
            self.lineage.mark_archived(
                tag, now if now is not None else time.time(),
                degraded=degraded)
        self.archived_total += 1
        # keep FIFO across the memory/disk boundary: once anything
        # spilled, later batches must spill too or replay reorders
        if self._archive_spill or len(self.archive) >= self.max_archive:
            fn = self._spill_path()
            with open(fn, "wb") as f:
                pickle.dump(_to_host(et), f, pickle.HIGHEST_PROTOCOL)
            self._archive_spill.append(fn)
            self.telemetry.count("archive.spilled")
        else:
            self.archive.append(et)

    def _archive_refill(self) -> None:
        """Pull spilled batches back into memory headroom, in order."""
        while self._archive_spill and len(self.archive) < self.max_archive:
            fn = self._archive_spill.pop(0)
            with open(fn, "rb") as f:
                self.archive.append(pickle.load(f))
            os.unlink(fn)

    # ------------------------------------------------------------------
    def push(self, et: EdgeTable, now: Optional[float] = None) -> dict:
        """GRAPHPUSH: pool admission + commit.  Returns commit stats."""
        tag, self._lineage_next = self._lineage_next, None
        wall = now if now is not None else time.time()
        if self.retry_policy is not None and self.degraded:
            if wall < self.next_retry_t:
                # degraded mode: the store is down and the backoff gate
                # is closed — preserve the batch without a doomed probe
                self._archive_put(et, tag, now=wall, degraded=True)
                return {"committed": False, "archived": self.archive_depth,
                        "degraded": True}
        if len(self.pool) >= self.max_pool_size:
            if len(self.pool) >= self.pool_cap:
                # hard cap: divert to the archive instead of unbounded
                # pool growth under sustained failure
                self.pool_overflows += 1
                self._archive_put(et, tag, now=wall)
                return {"committed": False, "pooled": len(self.pool),
                        "pool_overflow": self.pool_overflows}
            # pool full: hold in local memory until timeout (paper §III-B)
            self.pool.append(et)
            if self.lineage is not None and tag is not None:
                self._pool_tags.append(tag)
                self.lineage.mark_pooled(tag, wall)
            return {"committed": False, "pooled": len(self.pool)}
        self.pool.append(et)
        if self.lineage is not None and tag is not None:
            self._pool_tags.append(tag)
        stats = {}
        while self.pool:
            batch = self.pool.popleft()
            btag = self._pool_tags.popleft() if self._pool_tags else None
            stats = self._commit(batch, now, tag=btag)
            if not stats["committed"]:
                break
        return stats

    def _commit(self, et: EdgeTable, now: Optional[float],
                archive_on_fail: bool = True, tag=None) -> dict:
        tel = self.telemetry
        wall = now if now is not None else time.time()
        t0 = time.perf_counter()
        self.attempts += 1
        try:
            if self.fail_hook is not None:
                fh = self.fail_hook
                hit = fh(wall) if getattr(fh, "wants_now", False) else fh()
                if hit:
                    raise ConnectionError("injected commit failure")
            compressed = hasattr(et, "residual")
            with tel.span("commit.upsert"):
                if compressed:
                    # pattern-aware path: repro.compress.CompressedCommit
                    new_store, s = commit_compressed(self.store, et)
                else:
                    new_store, s = ingest_step(self.store, et)
            with tel.span("commit.wait"):
                jax.block_until_ready(new_store.n_nodes)
            self.store = new_store
            busy = time.perf_counter() - t0
            self._busy.append((wall, busy))
            self.consecutive_failures = 0
            self.next_retry_t = float("-inf")
            with tel.span("commit.fetch"):
                nrun, erun, nneed, eneed = jax.device_get(
                    [s[k] for k in _ROUND_KEYS])
                instructions = int(s["instructions"])
                new_nodes = int(s["new_nodes"])
                rec = CommitRecord(
                    t=wall,
                    busy_s=busy,
                    instructions=instructions,
                    new_nodes=new_nodes,
                    batch_nodes=int(s["batch_nodes"]),
                    ok=True,
                    probe_rounds=int(s["probe_rounds"]),
                    dropped=int(s["dropped_inserts"]),
                    refs=int(s["dict_refs"]) if compressed else 0,
                    rounds_run=int(nrun) + int(erun),
                    rounds_needed=int(nneed) + int(eneed),
                    # every edge of the table holds one of these lanes
                    lanes_idle=(et.lanes - (instructions - new_nodes)
                                if compressed else 0),
                )
                pressure = max(float(s["node_load"]), float(s["edge_load"]))
                hit_rate = float(s["dict_hit_rate"]) if compressed else None
            self.commits.append(rec)
            if self.lineage is not None and tag is not None:
                # store took it: the committed low watermark may advance
                self.lineage.mark_committed(tag, wall)
            with tel.span("commit.hooks"):
                if self.commit_hook is not None:
                    self.commit_hook(et, s)
                for hook in self.commit_hooks:
                    hook(et, s)
            if self.lineage is not None and tag is not None:
                # the hook fan-out (snapshot maintainer absorb + sketch
                # update) has run: queries can now SEE these records —
                # only here does the queryable watermark advance
                self.lineage.mark_queryable(tag, wall)
            # the commit outcome: the same keys on every successful commit
            return {
                "committed": True,
                "stats": s,
                "busy_s": busy,
                "rho": rec.new_nodes / max(rec.batch_nodes, 1),
                "instructions": rec.instructions,
                # table-pressure signals for the Algorithm-2 controller
                "dropped": rec.dropped,
                "probe_rounds": rec.probe_rounds,
                "pressure": pressure,
                # compressibility signals (repro.compress -> controller);
                # 0 and None for a table the dictionary did not rewrite
                "refs": rec.refs,
                "dict_hit_rate": hit_rate,
            }
        except ConnectionError:
            # commit failed (network/DBMS) -> archive for replay.
            # `wall`, not `now or time.time()`: now=0.0 is falsy, so the
            # old form stamped simulated-clock failures with wall time.
            self.consecutive_failures += 1
            out = {"committed": False}
            if self.retry_policy is not None:
                delay = self.retry_policy.delay(self.consecutive_failures - 1)
                self.next_retry_t = wall + delay
                out["retry_in_s"] = delay
                tel.count("retry.backoff")
                if self.degraded:
                    out["degraded"] = True
            if archive_on_fail:
                self._archive_put(et, tag, now=wall,
                                  degraded=bool(out.get("degraded")))
            self.commits.append(
                CommitRecord(wall, 0.0, 0, 0, 0, ok=False)
            )
            out["archived"] = self.archive_depth
            return out

    # ------------------------------------------------------------------
    def retry_archive(self, now: Optional[float] = None) -> int:
        """Re-commit archived batches (connection restored).  With a
        `RetryPolicy` attached the backoff gate is honoured: while
        `now < next_retry_t` nothing is attempted (no hot-looping);
        one probe failure re-arms the gate with the next delay."""
        if self.retry_policy is not None:
            wall = now if now is not None else time.time()
            if wall < self.next_retry_t:
                return 0
        n = 0
        while self.archive_depth:
            self._archive_refill()
            et = self.archive.popleft()
            tag = None
            if self.lineage is not None and self._archive_tags:
                tag = self._archive_tags.popleft()
                self.lineage.mark_replay(
                    tag, now if now is not None else time.time())
            if self._commit(et, now, archive_on_fail=False,
                            tag=tag)["committed"]:
                n += 1
                self.replayed += 1
                continue
            # failed head returns to the FRONT: replay order is FIFO
            self.archive.appendleft(et)
            if tag is not None:
                self._archive_tags.appendleft(tag)
            break
        if n:
            self.telemetry.count("retry.replayed", n)
        return n

    def occupancy(self, now: float, sim_busy: Optional[float] = None) -> float:
        """mu in [0,1]: ingest busy-fraction over the trailing window."""
        w0 = now - self.occupancy_window
        busy = sum(b for (t, b) in self._busy if t >= w0)
        return min(busy / self.occupancy_window, 1.0)

    def pending_work_s(self) -> float:
        """Estimated seconds of work queued in the pool (system-delay
        alpha for the measured path): pooled batches x mean commit
        cost over the busy window."""
        busy = [b for (_, b) in self._busy]
        mean_busy = sum(busy) / len(busy) if busy else 0.0
        return len(self.pool) * mean_busy

    # ---- checkpoint surface (repro.resilience) -----------------------
    def state(self) -> dict:
        """Everything except `store` (which snapshots as array leaves):
        pool/archive batches as host pytrees, archive spill CONTENTS
        (the files may be gone by restore time), counters, the backoff
        gate, and the fault injector's attempt counter when present."""
        spilled = []
        for fn in self._archive_spill:
            with open(fn, "rb") as f:
                spilled.append(f.read())  # already-pickled host pytree
        fh = self.fail_hook
        return {
            "pool": [_to_host(et) for et in self.pool],
            "archive": [_to_host(et) for et in self.archive],
            "archive_spill": spilled,
            "archive_n": self._archive_n,
            "commits": list(self.commits),
            "busy": list(self._busy),
            "attempts": self.attempts,
            "archived_total": self.archived_total,
            "replayed": self.replayed,
            "pool_overflows": self.pool_overflows,
            "consecutive_failures": self.consecutive_failures,
            "next_retry_t": self.next_retry_t,
            "fail_hook": fh.state() if hasattr(fh, "state") else None,
            "pool_tags": list(self._pool_tags),
            "archive_tags": list(self._archive_tags),
            "lineage_next": self._lineage_next,
        }

    def restore_state(self, s: dict) -> None:
        self.pool = collections.deque(s["pool"])
        self.archive = collections.deque(s["archive"])
        self._archive_spill = []
        self._archive_n = int(s["archive_n"])
        for blob in s["archive_spill"]:
            # rewrite under fresh (still-monotone) names: the original
            # files may live in a dead temp dir or have been drained
            fn = self._spill_path()
            with open(fn, "wb") as f:
                f.write(blob)
            self._archive_spill.append(fn)
        self.commits = list(s["commits"])
        self._busy = collections.deque(s["busy"], maxlen=self._busy.maxlen)
        self.attempts = int(s["attempts"])
        self.archived_total = int(s["archived_total"])
        self.replayed = int(s["replayed"])
        self.pool_overflows = int(s["pool_overflows"])
        self.consecutive_failures = int(s["consecutive_failures"])
        self.next_retry_t = float(s["next_retry_t"])
        if s.get("fail_hook") is not None \
                and hasattr(self.fail_hook, "restore_state"):
            self.fail_hook.restore_state(s["fail_hook"])
        # .get: checkpoints written before lineage landed lack these
        self._pool_tags = collections.deque(s.get("pool_tags", ()))
        self._archive_tags = collections.deque(s.get("archive_tags", ()))
        self._lineage_next = s.get("lineage_next")

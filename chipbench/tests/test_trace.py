"""The reduction from a trace to per-layer numbers."""
import dataclasses
from pathlib import Path

import pytest

from chipbench import trace as tr

# Four commits of `ingest_step` at the firehose store (2^26 node and
# 2^27 edge slots, 8,192-edge tables) and four `sketch_update` calls,
# recorded on one TPU v5e by the JAX profiler.  The harness's
# `chipbench.*` spans are not in it; the test adds the window.
RECORDED = Path(__file__).resolve().parent / "data" / "firehose_commits.xplane.pb"


def synthetic():
    return tr.Trace(
        modules=[("jit_ingest_step", 1.0, 1.2), ("jit_ingest_step", 1.5, 1.6),
                 ("jit_sketch_update", 1.55, 1.7), ("jit_x", 0.5, 0.9)],
        ops=[("while", 1.05, 1.15), ("fusion", 1.06, 1.07),
             ("while", 1.52, 1.58), ("while", 1.6, 1.65)],
        host=[("chipbench.window", 0.8, 2.0), ("chipbench.commit", 0.95, 1.25),
              ("chipbench.poll", 1.3, 1.45), ("chipbench.poll", 1.75, 2.0)],
        n_chips=1)


def test_interval_arithmetic():
    assert tr.merge([(0, 2), (1, 3), (5, 6)], 0.5, 5.5) == [(0.5, 3), (5, 5.5)]
    assert tr.gaps([(1, 2), (3, 4)], 0, 5) == [(0, 1), (2, 3), (4, 5)]
    assert tr.op_kind("%while.13 = (s32[]) while(...)") == "while"
    assert tr.op_kind("%fusion.64 = (u32[8]) fusion(...)") == "fusion"


def test_reduction_of_a_synthetic_trace():
    r = tr.Reduced(synthetic())
    assert r.window_s == pytest.approx(1.2)
    # busy: [0.8,0.9] [1.0,1.2] [1.5,1.7]
    assert r.busy_s == pytest.approx(0.1 + 0.2 + 0.2)
    s, n = r.module_seconds(["jit_ingest_step"])
    assert n == 2 and s == pytest.approx(0.3)
    # the while at 1.6-1.65 ran inside sketch_update, not a commit
    assert r.op_seconds_within("while", ["jit_ingest_step"]) == \
        pytest.approx(0.1 + 0.06)
    assert r.device_ops()[0][0] == "jit_ingest_step"
    gaps = dict(r.idle_gaps())
    # idle: [0.9,1.0] (commit 0.05, loop 0.05), [1.2,1.5] (commit 0.05,
    # poll 0.15, loop 0.1), [1.7,2.0] (poll 0.25, loop 0.05)
    assert gaps["chipbench.commit"] == pytest.approx(0.1)
    assert gaps["chipbench.poll"] == pytest.approx(0.4)
    assert gaps["pipeline loop (no harness span)"] == pytest.approx(0.2)
    assert sum(gaps.values()) == pytest.approx(r.window_s - r.busy_s)


def test_a_trace_without_chip_or_window_is_refused():
    t = synthetic()
    with pytest.raises(ValueError):
        tr.Reduced(tr.Trace(t.modules, t.ops, t.host, n_chips=0))
    with pytest.raises(ValueError):
        tr.Reduced(tr.Trace(t.modules, t.ops, t.host[1:], n_chips=1))


def test_reduction_of_a_recorded_trace():
    t = tr.load(str(RECORDED))
    assert t.n_chips == 1 and t.host == []
    lo = min(a for _, a, _ in t.modules) - 0.01
    hi = max(b for _, _, b in t.modules) + 0.01
    r = tr.Reduced(dataclasses.replace(t, host=[(tr.WINDOW, lo, hi)]))
    s, n = r.module_seconds(["jit_ingest_step"])
    assert n == 4 and 0.15 < s / n < 0.25  # ~190 ms a commit
    assert r.module_seconds(["jit_sketch_update"])[1] == 4
    # the node and the edge probe sweeps: two `while` loops a commit
    w = r.op_seconds_within("while", ["jit_ingest_step"])
    assert 0.5 * s < w < s
    assert r.op_seconds_within("while", ["jit_sketch_update"]) < 0.001 * w
    assert 0 < r.busy_s < r.window_s
    assert r.device_ops()[0][0] == "jit_ingest_step"
    gaps = dict(r.idle_gaps())
    assert list(gaps) == ["pipeline loop (no harness span)"]
    assert sum(gaps.values()) == pytest.approx(r.window_s - r.busy_s)

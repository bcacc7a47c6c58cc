"""CPU-only fixtures for the benchmark's own tests.

    JAX_PLATFORMS=cpu python -m pytest chipbench/tests

Nothing here or in the tests touches a TPU: runs go through
`harness.run(..., require_tpu=False)` at tiny capacities."""
import json
import os
import shutil
import sys
import time
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
CHECKOUT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(CHECKOUT / "src"), str(CHECKOUT)]

import jax  # noqa: E402
import pytest  # noqa: E402

jax.config.update("jax_enable_x64", True)

# tiny capacities: the cells' shapes of traffic and path, CPU-sized.  A
# tiny window adds far more to the store, relative to its size, than a
# real one does, so the fill is smaller here: the edge table ends near
# 0.35 load, below the ~0.46 where the program's 32-round probe budget
# starts to drop inserts (PERF.md, Open questions)
TINY = {
    "firehose": {"rate_events_per_s": 3000, "poll_max_events": 64,
                 "ingest": {"store_nodes": 1 << 16, "store_edges": 1 << 17,
                            "max_edges_per_batch": 256,
                            "max_nodes_per_batch": 256},
                 "prefill": {"edges": 16384, "nodes": 5461,
                             "chunk_edges": 4096}},
    "paper1pct": {"poll_max_events": 64, "warm_events": {"each_up_to": 8,
                                                    "pads_up_to": 64},
                  "ingest": {"store_nodes": 1 << 16, "store_edges": 1 << 17},
                  "prefill": {"edges": 32768, "nodes": 11000,
                              "chunk_edges": 4096}},
}


# `paper1pct.flash_crowd` is held out of BENCHMARK.json while the
# program's dictionary path compiles inside its window (PERF.md, Open
# questions); its entries live here, and the tests keep driving it
HELD_BACK = Path(__file__).resolve().parent / "data" / "paper1pct_cell.json"


def full_manifest() -> dict:
    """BENCHMARK.json with the held-back cell's entries added."""
    m = json.loads((CHECKOUT / "BENCHMARK.json").read_text())
    for key, entries in json.loads(HELD_BACK.read_text()).items():
        m[key] = m[key] + entries
    return m


def make_root(dst: Path) -> Path:
    """A copy of the manifest and its data files with tiny configs."""
    (dst / "BENCHMARK.json").write_text(json.dumps(full_manifest()))
    for sub in ("traffic", "metrics", "configs"):
        shutil.copytree(CHECKOUT / "chipbench" / sub, dst / "chipbench" / sub)
    for name, over in TINY.items():
        p = dst / "chipbench" / "configs" / f"{name}.json"
        cfg = json.loads(p.read_text())
        for k, v in over.items():
            cfg[k] = {**cfg[k], **v} if isinstance(v, dict) else v
        p.write_text(json.dumps(cfg))
    return dst


@pytest.fixture
def tiny_root(tmp_path):
    return make_root(tmp_path)


def run_tiny(root: Path, workload: str, seed: int = 2**31 + 7,
             seconds: float = 2.0) -> dict:
    from chipbench import harness
    from chipbench.compile_log import CompileLog

    device = {"platform": "cpu", "kind": "cpu", "count": 1}
    out, _ = harness.run(root, workload, seed, seconds, False,
                         time.perf_counter(), CompileLog(), device,
                         require_tpu=False)
    return out

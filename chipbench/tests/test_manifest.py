"""Cells, configurations, traffic mixes and metrics are found by name:
adding one is adding files and manifest entries, with no edit to the
harness."""
import json
import shutil
from pathlib import Path

import pytest

from chipbench.manifest import load_cell
from conftest import full_manifest

CHECKOUT = Path(__file__).resolve().parents[2]


def test_every_named_file_exists(tmp_path):
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(full_manifest()))
    shutil.copytree(CHECKOUT / "chipbench", tmp_path / "chipbench")
    for w in full_manifest()["workloads"]:
        cell = load_cell(tmp_path, w["name"])
        assert cell.end_to_end and cell.per_layer
        assert any(x.name == "setup_s" for x in cell.end_to_end)


def test_added_files_are_found_by_name(tmp_path):
    root = tmp_path
    (root / "BENCHMARK.json").write_text(json.dumps(full_manifest()))
    for sub in ("traffic", "metrics", "configs"):
        shutil.copytree(CHECKOUT / "chipbench" / sub, root / "chipbench" / sub)
    before = {p: p.read_bytes() for p in (root / "chipbench").rglob("*")
              if p.is_file()}
    # a new deployment, a new mix, a new per-layer metric: new files only
    cfg = json.loads((root / "chipbench/configs/paper1pct.json").read_text())
    cfg.update(name="paper10pct", rate_events_per_s=600)
    (root / "chipbench/configs/paper10pct.json").write_text(json.dumps(cfg))
    (root / "chipbench/traffic/spam_storm.json").write_text(json.dumps(
        {"duplicate_frac": 0.5, "zipf_user": 2.5, "zipf_tag": 2.0}))
    (root / "chipbench/metrics/ticks_per_s.lat.py").write_text(
        "def read(ctx):\n    return ctx.ticks_in_window / ctx.seconds\n")
    m = json.loads((root / "BENCHMARK.json").read_text())
    m["configs"].append({"name": "paper10pct", "source": "x",
                         "file": "chipbench/configs/paper10pct.json",
                         "reduced": [], "why": "x"})
    m["workloads"].append({"name": "paper10pct.spam_storm",
                           "config": "paper10pct", "traffic": "spam_storm",
                           "chips": 1, "why": "x"})
    m["per_layer"].append({"name": "ticks_per_s.lat", "unit": "1/s",
                           "better": "higher", "source": "host_clock",
                           "layer": "pipeline loop (api.pipeline)",
                           "moves": "latency_p95_ms",
                           "workloads": ["paper10pct.spam_storm"]})
    (root / "BENCHMARK.json").write_text(json.dumps(m))

    cell = load_cell(root, "paper10pct.spam_storm")
    assert cell.config["rate_events_per_s"] == 600
    assert cell.traffic["duplicate_frac"] == 0.5
    assert cell.traffic["noise_frac"] == 0.25  # the family's default
    names = [x.name for x in cell.per_layer]
    assert "ticks_per_s.lat" in names
    ctx = type("Ctx", (), {"ticks_in_window": 30, "seconds": 3.0})()
    reader = {x.name: x.reader for x in cell.per_layer}["ticks_per_s.lat"]
    assert reader(ctx) == 10.0
    # the old files are untouched, and the old cells still load
    for p, data in before.items():
        assert p.read_bytes() == data
    assert "ticks_per_s.lat" not in [
        x.name for x in load_cell(root, "paper1pct.flash_crowd").per_layer]


def test_unknown_workload_is_refused():
    with pytest.raises(KeyError):
        load_cell(CHECKOUT, "no.such_cell")

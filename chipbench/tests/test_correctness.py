"""`correct` comes out true for a sound run and false for the control
and for each fault the cells can have, with the rest of a run driven as
on the chip (tiny capacities, CPU, no look for a chip)."""
import dataclasses
import json
import subprocess
import sys

import jax.numpy as jnp
import pytest

from conftest import CHECKOUT, make_root, run_tiny


def test_sound_runs_are_correct(tiny_root):
    for cell in ("firehose.steady", "paper1pct.flash_crowd"):
        out = run_tiny(tiny_root, cell)
        assert out["correct"], (cell, out["checks"])
        assert out["attempted"] > 0 and out["failed"] == 0
        assert set(out["metrics"]) >= {"setup_s"}
        assert list(out)[-1] == "checks"


def _table(et, change):
    """Apply `change` to the table a commit writes: the residual of a
    dictionary-compressed commit, else the edge table itself."""
    if hasattr(et, "residual"):
        return dataclasses.replace(et, residual=change(et.residual))
    return change(et)


def _unchanged(real):
    def step(store, et):
        _, stats = real(store, et)
        return store, stats
    return step


def _half_batch(real):
    def drop_odd(t):
        lane = jnp.arange(t.edge_valid.shape[0])
        return dataclasses.replace(t, edge_valid=t.edge_valid & (lane % 2 == 0))

    return lambda store, et: real(store, _table(et, drop_odd))


def _count_altered(real):
    def bump(t):
        return dataclasses.replace(t, count=t.count.at[0].add(1))

    return lambda store, et: real(store, _table(et, bump))


@pytest.mark.parametrize("cell", ["firehose.steady", "paper1pct.flash_crowd"])
@pytest.mark.parametrize("fault", [_unchanged, _half_batch, _count_altered],
                         ids=["state_unchanged", "half_batch", "answer_altered"])
def test_faults_are_caught(tiny_root, monkeypatch, fault, cell):
    import repro.core.ingestor as ingestor

    for step in ("ingest_step", "commit_compressed"):
        monkeypatch.setattr(ingestor, step, fault(getattr(ingestor, step)))
    out = run_tiny(tiny_root, cell)
    assert not out["correct"]
    assert out["checks"]["edges_mismatched"]["value"] > 0


@pytest.mark.parametrize("cell", ["firehose.steady", "paper1pct.flash_crowd"])
def test_a_lost_slot_of_the_past_is_caught_by_the_totals(tiny_root,
                                                          monkeypatch, cell):
    import repro.core.ingestor as ingestor

    def lose_first_slot(real):
        def step(store, et):
            new, stats = real(store, et)
            first = jnp.argmax(new.edge_keys != 0)
            return dataclasses.replace(
                new, edge_keys=new.edge_keys.at[first].set(0)), stats
        return step

    for step in ("ingest_step", "commit_compressed"):
        monkeypatch.setattr(ingestor, step,
                            lose_first_slot(getattr(ingestor, step)))
    out = run_tiny(tiny_root, cell)
    assert not out["correct"]
    assert out["checks"]["totals_mismatched"]["value"] > 0


def test_lower_precision_control_fails_and_cpu_reports_nothing(tmp_path):
    root = make_root(tmp_path)
    base = [sys.executable, str(CHECKOUT / "chipbench" / "run.py"),
            "--workload", "firehose.steady", "--seed", str(2**31 + 99),
            "--seconds", "2", "--trace", "0", "--allow-cpu",
            "--root", str(root)]
    env = {"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin",
           "HOME": str(tmp_path)}
    sound = subprocess.run(base, capture_output=True, text=True, env=env,
                           timeout=600)
    control = subprocess.run(base + ["--control", "keys32"],
                             capture_output=True, text=True, env=env,
                             timeout=600)
    for r in (sound, control):
        assert r.returncode == 3, r.stderr[-2000:]
        for line in r.stdout.splitlines():  # no result line on a CPU
            with pytest.raises(json.JSONDecodeError):
                json.loads(line)
    assert "correct: True" in sound.stdout
    assert "correct: False" in control.stdout
    assert "check edges_mismatched: 0 (limit 0)" in sound.stderr
    assert "check edges_mismatched: 0 (limit 0)" not in control.stderr


def test_no_tpu_means_no_result(tmp_path):
    r = subprocess.run(
        [sys.executable, str(CHECKOUT / "chipbench" / "run.py"),
         "--workload", "firehose.steady", "--seed", "1", "--seconds", "1",
         "--trace", "0"],
        capture_output=True, text=True, timeout=300,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin",
             "HOME": str(tmp_path)})
    assert r.returncode != 0 and r.stdout.strip() == ""

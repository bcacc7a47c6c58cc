"""The program's spans and named device phases in a trace."""
from pathlib import Path

import pytest

from chipbench import program_trace as pt
from chipbench import trace as tr
from test_trace import synthetic

RECORDED = Path(__file__).resolve().parent / "data" / "firehose_commits.xplane.pb"


def test_without_program_spans_the_gaps_are_the_harness_ones():
    r = tr.Reduced(synthetic())
    got = pt.named_idle_gaps(r, pt.ProgramTrace([], []))
    assert dict(got) == pytest.approx(dict(r.idle_gaps()))


def test_idle_gaps_are_named_by_the_innermost_program_span():
    r = tr.Reduced(synthetic())
    # idle: [0.9,1.0], [1.2,1.5], [1.7,2.0]; chipbench.commit 0.95-1.25,
    # poll 1.3-1.45 and 1.75-2.0
    program = pt.ProgramTrace(spans=[
        ("repro.tick", 0.85, 1.28),              # holds the commit
        ("repro.commit.fetch", 1.21, 1.24),      # inside it, and the commit
        ("repro.loop.fetch", 1.25, 1.27),        # after the commit ends
        ("repro.tick", 1.46, 1.6),               # a tick with no harness span
    ], ops=[])
    got = dict(pt.named_idle_gaps(r, program))
    want = {
        "repro.tick": 0.05 + 0.01 + 0.04,  # from 0.9, 1.27 and 1.46
        "chipbench.commit/repro.tick": 0.05 + 0.01 + 0.01,  # 0.95, 1.2, 1.24
        "chipbench.commit/repro.commit.fetch": 0.03,
        "repro.loop.fetch": 0.02,
        "chipbench.poll": 0.15 + 0.25,
        "pipeline loop (no harness span)": 0.02 + 0.01 + 0.05,
    }
    assert got == pytest.approx(want)
    assert sum(got.values()) == pytest.approx(r.window_s - r.busy_s)


def test_scope_seconds_unions_ops_under_the_scopes_of_named_programs():
    t = synthetic()
    r = tr.Reduced(t)
    names = {"jit_ingest_step": {
        "while.13": "jit(ingest_step)/node_upsert/jit(f)/while",
        "fusion.2": "jit(ingest_step)/node_upsert/jit(f)/while/body/add",
        "while.17": "jit(ingest_step)/edge_upsert/jit(f)/while",
        "fusion.9": "jit(ingest_step)/store_scatter/scatter-add"}}
    program = pt.ProgramTrace(spans=[], ops=[
        ("while.13", 1.05, 1.10), ("fusion.2", 1.06, 1.07),  # nested
        ("fusion.9", 1.11, 1.12), ("while.17", 1.52, 1.55),
        ("copy.1", 1.56, 1.57),                            # not in the map
        ("while.13", 1.61, 1.65)])  # inside sketch_update, not a commit
    s, unmapped = pt.scope_seconds(r, program, names,
                                   ["node_upsert", "edge_upsert"],
                                   ["jit_ingest_step"])
    assert s == pytest.approx(0.05 + 0.03) and unmapped == 1
    s, _ = pt.scope_seconds(r, program, names, ["store_scatter"],
                            ["jit_ingest_step"])
    assert s == pytest.approx(0.01)
    # a scope is a whole component of the path, not a substring
    assert pt.scope_seconds(r, program, names, ["upsert"],
                            ["jit_ingest_step"])[0] == 0


def test_op_names_come_from_the_compiled_text():
    import jax
    import jax.numpy as jnp

    @jax.jit
    def f(x):
        with jax.named_scope("phase_a"):
            y = jnp.sin(x) * 2
        with jax.named_scope("phase_b"):
            return jnp.cumsum(y)

    text = f.lower(jnp.zeros(64)).compile().as_text()
    names = pt.op_names(text)
    scopes = {p for v in names.values() for p in v.split("/")}
    assert {"phase_a", "phase_b"} <= scopes
    assert all(f"%{k} = " in text for k in names)
    # every instruction is named, with "" where it has no op_name
    assert pt.op_names('  %copy.1 = f32[4]{0} copy(f32[4]{0} %p)\n'
                       '  ROOT %add.2 = f32[4]{0} add(%a, %b), '
                       'metadata={op_name="jit(f)/phase_a/add"}') == {
        "copy.1": "", "add.2": "jit(f)/phase_a/add"}


def test_a_recorded_trace_gives_ops_by_instruction_name():
    p = pt.load(str(RECORDED))
    assert p.spans == []  # recorded before the program wrote spans
    assert "while.13" in {n for n, _a, _b in p.ops}
    assert "device_duration_ps" in p.op_stats

#!/usr/bin/env python3
"""The program's own spans and named device phases in a profiler trace.

    python3 chipbench/program_trace.py --workload <cell> --seed <n> --seconds <s>

Runs one traced run of the cell exactly as `chipbench/run.py --trace 1`
does (its lines are printed as they are), and from the same trace file
prints one more line, `program_trace: {...}`:

  idle_gaps       the window's idle device time, each interval named by
                  the harness span and the innermost program span over
                  it (`chipbench.transform/repro.transform.map`);
  scopes_ms       device time per commit under each named phase of
                  `ingest_step` (`node_upsert`, `edge_upsert`, ...);
  while_ms        the top-level `while` ops per commit, as
                  `upsert_roofline` reads them, to compare;
  scope_source    where each op's phase came from.

Program spans are the `repro.*` annotations that an enabled
`repro.telemetry` registry writes.  Op events in the TPU trace carry
the HLO instruction's text and no phase, so the phase comes from the
commit program's compiled text, whose `metadata={op_name=...}` names
each instruction's scopes; instruction names repeat across programs, so
the map is kept per program.  This is a diagnostic beside the benchmark:
`chipbench/run.py` does not import it."""
from __future__ import annotations

import collections
import dataclasses
import json
import re
import sys
from pathlib import Path
from typing import Dict, Iterable, List, Tuple

if __package__ in (None, ""):
    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from chipbench import run  # noqa: E402
from chipbench import trace as tr  # noqa: E402

PROGRAM = "repro."
NO_SPAN = "pipeline loop (no harness span)"
COMMIT_PHASES = ("node_upsert", "edge_upsert", "store_scatter",
                 "degree_update", "ref_apply")
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%([\w.\-]+)\s*=(.*)$", re.M)
_OP_NAME = re.compile(r"metadata=\{[^}]*?op_name=\"([^\"]*)\"")


@dataclasses.dataclass
class ProgramTrace:
    spans: List[Tuple[str, float, float]]  # repro.* host annotations
    ops: List[Tuple[str, float, float]]    # (HLO instruction name, t0, t1)
    op_stats: List[str] = dataclasses.field(default_factory=list)


def load(path: str) -> ProgramTrace:
    """The program's spans, every device op by instruction name, and
    the names of the stats that the op events carry."""
    from jax.profiler import ProfileData

    spans, ops, stats = [], [], set()
    for plane in ProfileData.from_file(path).planes:
        device = plane.name.startswith("/device:TPU:")
        if not (device or plane.name.startswith("/host:")):
            continue
        for line in plane.lines:
            if device and line.name != "XLA Ops":
                continue
            for e in line.events:
                t0, t1 = e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9
                if device:
                    ops.append((e.name.split("=", 1)[0].strip().lstrip("%"),
                                t0, t1))
                    stats.update(k for k, _v in e.stats)
                elif e.name.startswith(PROGRAM):
                    spans.append((e.name, t0, t1))
    return ProgramTrace(spans, ops, sorted(stats))


def op_names(hlo_text: str) -> Dict[str, str]:
    """Instruction name -> its `op_name` (the scopes it was traced
    under; "" for an instruction with none, such as a copy the compiler
    added), from a compiled program's text (`Compiled.as_text()`)."""
    out = {}
    for m in _INSTR.finditer(hlo_text):
        op = _OP_NAME.search(m.group(2))
        out[m.group(1)] = op.group(1) if op else ""
    return out


def _innermost(spans: List[Tuple[str, float, float]], t: float):
    """The span covering `t` that started last: with spans nested by
    time, the innermost."""
    best = None
    for n, a, b in spans:
        if a <= t < b and (best is None or a >= best[1]):
            best = (n, a, b)
    return best[0] if best else None


def named_idle_gaps(reduced: tr.Reduced, program: ProgramTrace,
                    top: int = 20) -> List[list]:
    """Idle device time in the window, each piece named
    `<harness span>/<program span>` by the innermost span of each kind
    over it; the harness span alone where no program span covers it,
    the program span alone outside every harness span, and
    `pipeline loop (no harness span)` outside both.  With no program
    spans this is `Reduced.idle_gaps`."""
    harness = [s for s in reduced.trace.host if s[0] != tr.WINDOW]
    tot: Dict[str, float] = collections.Counter()
    for g0, g1 in tr.gaps(reduced.busy, reduced.lo, reduced.hi):
        hs = [s for s in harness if s[1] < g1 and s[2] > g0]
        ps = [s for s in program.spans if s[1] < g1 and s[2] > g0]
        cuts = sorted({g0, g1} | {t for _n, a, b in hs + ps for t in (a, b)
                                  if g0 < t < g1})
        for x0, x1 in zip(cuts, cuts[1:]):
            mid = 0.5 * (x0 + x1)
            names = (_innermost(hs, mid), _innermost(ps, mid))
            tot["/".join(n for n in names if n) or NO_SPAN] += x1 - x0
    return [[n, s] for n, s in sorted(tot.items(), key=lambda x: -x[1])
            [:top]]


def scope_seconds(reduced: tr.Reduced, program: ProgramTrace,
                  names: Dict[str, Dict[str, str]], scopes: Iterable[str],
                  programs: Iterable[str]) -> Tuple[float, int]:
    """Device seconds under any of `scopes`, inside executions of
    `programs` in the window: the union of the intervals of the ops
    whose `op_name` holds one of the scopes.  `names` maps each program
    to its instruction names' `op_name` (`op_names`).  Also returns the
    number of ops inside those executions whose instruction the map
    lacks."""
    scopes = set(scopes)
    execs = sorted((a, b, n) for n, a, b in reduced.trace.modules
                   if n in set(programs) and a >= reduced.lo
                   and b <= reduced.hi)
    hit, unmapped, k = [], 0, 0
    for instr, a, b in sorted(program.ops, key=lambda o: o[1]):
        while k < len(execs) and execs[k][1] < a:
            k += 1
        if k == len(execs) or not (execs[k][0] <= a and b <= execs[k][1]):
            continue
        op_name = names.get(execs[k][2], {}).get(instr)
        if op_name is None:
            unmapped += 1
        elif scopes & set(op_name.split("/")):
            hit.append((a, b))
    return tr.length(tr.merge(hit, reduced.lo, reduced.hi)), unmapped


def commit_op_names(cfg: Dict) -> Dict[str, Dict[str, str]]:
    """`ingest_step`'s instruction names at the window's shapes: the
    configured store and a full table.  Compiling it again is a cache
    hit."""
    import jax
    import jax.numpy as jnp
    from repro.core.compression import key_dtype
    from repro.core.edge_table import build_edge_table
    from repro.graphstore.store import ingest_step, init_store

    ing, kd = cfg["ingest"], key_dtype()
    vec = lambda d: jax.ShapeDtypeStruct((ing["max_edges_per_batch"],), d)
    et = jax.eval_shape(build_edge_table, vec(kd), vec(kd), vec(jnp.int32),
                        vec(jnp.bool_))
    store = jax.eval_shape(lambda: init_store(
        ing["store_nodes"], ing["store_edges"], key_dtype=kd))
    text = ingest_step.lower(store, et).compile().as_text()
    return {"jit_ingest_step": op_names(text)}


def main(argv=None) -> int:
    import argparse

    from chipbench.manifest import load_cell
    from chipbench.readers import COMMIT_PROGRAMS

    args = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    args.add_argument("--workload", required=True)
    args.add_argument("--seed", type=int, required=True)
    args.add_argument("--seconds", type=float, required=True)
    args.add_argument("--root", type=Path, default=run.CHECKOUT)
    a = args.parse_args(argv)
    found: Dict = {}
    load_device_trace = tr.load

    def load_both(path):
        # the harness deletes its trace when the run ends: reduce the
        # program's side while the file is there
        found["device"] = load_device_trace(path)
        found["program"] = load(path)
        return found["device"]

    tr.load = load_both
    rc = run.main(["--workload", a.workload, "--seed", str(a.seed),
                   "--seconds", str(a.seconds), "--trace", "1",
                   "--root", str(a.root)])
    if rc or not found:
        return rc or run.NO_RESULT
    reduced = tr.Reduced(found["device"])
    program = found["program"]
    names = commit_op_names(load_cell(a.root, a.workload).config)
    _, n = reduced.module_seconds(COMMIT_PROGRAMS)
    per = lambda s: s / n * 1e3 if n else None
    out = {"idle_gaps": named_idle_gaps(reduced, program),
           "scope_source": "the compiled ingest_step's op_name metadata",
           "op_event_stats": program.op_stats, "scopes_ms": {},
           "while_ms": per(reduced.op_seconds_within("while",
                                                     COMMIT_PROGRAMS)),
           "commits": n, "program_spans": len(program.spans)}
    for scope in COMMIT_PHASES + ("node_upsert+edge_upsert",):
        s, out["unmapped_ops"] = scope_seconds(
            reduced, program, names, scope.split("+"), ["jit_ingest_step"])
        out["scopes_ms"][scope] = per(s)
    print("program_trace: " + json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

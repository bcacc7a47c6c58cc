"""Reduction from a profiler trace to the numbers the readers need.

The JAX profiler writes `<dir>/plugins/profile/<time>/*.xplane.pb`.
Device planes are named `/device:TPU:<n>`; on each, the line
`XLA Modules` holds one event per program execution, named
`<jit name>(<fingerprint>)`, and `XLA Ops` one event per HLO
instruction, named by the instruction's text (`%while.13 = ...`).
Host planes hold the `TraceAnnotation` spans that the harness writes
around its own calls (`chipbench.*`), on the same clock.

Everything here is plain interval arithmetic over those events, so a
test can check it on a small recorded trace (`tests/data`)."""
from __future__ import annotations

import collections
import dataclasses
import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Tuple

Interval = Tuple[float, float]  # seconds on the trace clock

PREFIX = "chipbench."
WINDOW = PREFIX + "window"
_FINGERPRINT = re.compile(r"\(\d+\)$")


@dataclasses.dataclass
class Trace:
    modules: List[Tuple[str, float, float]]   # (jit name, t0, t1), all chips
    ops: List[Tuple[str, float, float]]       # (HLO op name, t0, t1)
    host: List[Tuple[str, float, float]]      # chipbench.* annotations
    n_chips: int

    @property
    def window(self) -> Optional[Interval]:
        spans = [(t0, t1) for n, t0, t1 in self.host if n == WINDOW]
        return spans[0] if spans else None


def find_xplane(log_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return files[-1]


def op_kind(hlo_text: str) -> str:
    """`%while.13 = (...) while(...)` -> `while`; `%fusion.64 = ...` ->
    `fusion`."""
    head = hlo_text.split("=", 1)[0].strip().lstrip("%")
    return head.rstrip("0123456789").rstrip(".") or head


def load(path: str) -> Trace:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    modules, ops, host = [], [], []
    chips = 0
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            chips += 1
            for line in plane.lines:
                if line.name == "XLA Modules":
                    for e in line.events:
                        modules.append((_FINGERPRINT.sub("", e.name),
                                        e.start_ns * 1e-9,
                                        (e.start_ns + e.duration_ns) * 1e-9))
                elif line.name == "XLA Ops":
                    for e in line.events:
                        ops.append((op_kind(e.name), e.start_ns * 1e-9,
                                    (e.start_ns + e.duration_ns) * 1e-9))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(PREFIX):
                        host.append((e.name, e.start_ns * 1e-9,
                                     (e.start_ns + e.duration_ns) * 1e-9))
    return Trace(modules, ops, host, chips)


def merge(intervals: Iterable[Interval], lo: float, hi: float
          ) -> List[Interval]:
    """Union of intervals clipped to [lo, hi], sorted and disjoint."""
    out: List[List[float]] = []
    for a, b in sorted((max(a, lo), min(b, hi)) for a, b in intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def length(intervals: Iterable[Interval]) -> float:
    return sum(b - a for a, b in intervals)


def gaps(busy: List[Interval], lo: float, hi: float) -> List[Interval]:
    """The complement of disjoint sorted `busy` within [lo, hi]."""
    out, t = [], lo
    for a, b in busy:
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if t < hi:
        out.append((t, hi))
    return out


def overlap(a: Interval, b: Interval) -> float:
    return max(0.0, min(a[1], b[1]) - max(a[0], b[0]))


class Reduced:
    """A trace seen through the measured window."""

    def __init__(self, trace: Trace):
        if trace.n_chips == 0:
            raise ValueError("the trace holds no TPU plane")
        if trace.window is None:
            raise ValueError(f"the trace holds no {WINDOW} annotation")
        self.trace = trace
        self.lo, self.hi = trace.window
        self.busy = merge(((a, b) for _, a, b in trace.modules),
                          self.lo, self.hi)

    @property
    def window_s(self) -> float:
        return self.hi - self.lo

    @property
    def busy_s(self) -> float:
        """Seconds in which a program ran, averaged over the chips."""
        return length(self.busy) / self.trace.n_chips

    def modules(self, names: Iterable[str]) -> List[Interval]:
        names = set(names)
        return [(a, b) for n, a, b in self.trace.modules
                if n in names and a >= self.lo and b <= self.hi]

    def module_seconds(self, names: Iterable[str]) -> Tuple[float, int]:
        """Total device seconds and executions of the named programs that
        ran wholly inside the window."""
        spans = self.modules(names)
        return length(spans), len(spans)

    def op_seconds_within(self, kind: str, names: Iterable[str]) -> float:
        """Device seconds of top-level ops of one kind (e.g. `while`)
        that ran inside executions of the named programs."""
        spans = sorted(self.modules(names))
        total, k = 0.0, 0
        for n, a, b in sorted((o for o in self.trace.ops if o[0] == kind),
                              key=lambda o: o[1]):
            while k < len(spans) and spans[k][1] < a:
                k += 1
            if k < len(spans) and spans[k][0] <= a and b <= spans[k][1]:
                total += b - a
        return total

    def device_ops(self, top: int = 10) -> List[list]:
        """Programs that took the most device time in the window."""
        tot: Dict[str, float] = collections.Counter()
        for n, a, b in self.trace.modules:
            tot[n] += overlap((a, b), (self.lo, self.hi))
        return [[n, s] for n, s in sorted(tot.items(), key=lambda x: -x[1])
                [:top] if s > 0]

    def idle_gaps(self, top: int = 10) -> List[list]:
        """Idle device time, split by what the harness's host spans say
        the host was doing; time under none of them is the pipeline's
        own loop (controller, buffer, metrics)."""
        spans = [(n, a, b) for n, a, b in self.trace.host if n != WINDOW]
        spans.sort(key=lambda s: s[1])
        tot: Dict[str, float] = collections.Counter()
        k = 0  # the harness's spans follow one another without overlap
        for g in gaps(self.busy, self.lo, self.hi):
            while k < len(spans) and spans[k][2] <= g[0]:
                k += 1
            covered = 0.0
            for n, a, b in spans[k:]:
                if a >= g[1]:
                    break
                o = overlap(g, (a, b))
                if o > 0:
                    tot[n] += o
                    covered += o
            rest = (g[1] - g[0]) - covered
            if rest > 0:
                tot["pipeline loop (no harness span)"] += rest
        return [[n, s] for n, s in sorted(tot.items(), key=lambda x: -x[1])
                [:top]]

"""Reduction of a profiler trace to what the per-layer metrics read.

A trace is kept here in a plain form: per device, the device's operations
as (name, start, duration) in nanoseconds on the profiler's clock; and the
benchmark's own host spans (``jax.profiler.TraceAnnotation`` names that
start with ``bench.``) on the same clock.  ``load`` makes that form from
the ``.xplane.pb`` the profiler writes; ``save_json``/``load_json`` keep a
small one for the tests.  Every function takes a window ``(lo, hi)`` and
counts only what lies inside it.
"""
from __future__ import annotations

import dataclasses
import json
import re
from collections import defaultdict
from pathlib import Path

HOST_PREFIX = "bench."
# the line of a device plane that holds one event per operation run
OPS_LINE = "XLA Ops"
COLLECTIVE = re.compile(
    r"all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute")


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start: float        # ns
    dur: float          # ns

    @property
    def end(self) -> float:
        return self.start + self.dur


@dataclasses.dataclass
class Trace:
    devices: dict[str, list[Event]]
    host: list[Event]

    def span(self, name: str) -> tuple[float, float]:
        """(start, end) of the one host span called ``name``."""
        hits = [e for e in self.host if e.name == name]
        if len(hits) != 1:
            raise ValueError(f"{len(hits)} host spans named {name!r}")
        return hits[0].start, hits[0].end


def load(profile_dir: str | Path) -> Trace:
    """The newest ``.xplane.pb`` under ``profile_dir``, reduced."""
    from jax.profiler import ProfileData
    files = sorted(Path(profile_dir).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {profile_dir}")
    pd = ProfileData.from_file(str(files[-1]))
    devices: dict[str, list[Event]] = {}
    host: list[Event] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices[plane.name] = [
                        Event(op_name(e.name), e.start_ns, e.duration_ns)
                        for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host += [Event(e.name, e.start_ns, e.duration_ns)
                         for e in line.events
                         if e.name.startswith(HOST_PREFIX)]
    return Trace(devices=devices, host=sorted(host, key=lambda e: e.start))


def op_name(text: str) -> str:
    """The operation's own name from the HLO text an event carries
    (``%fusion.3 = bf16[...] fusion(%a, ...)`` -> ``fusion.3``), so that a
    pattern never matches an operand."""
    return text.split(" = ", 1)[0].lstrip("%")


def save_json(tr: Trace, path: str | Path) -> None:
    Path(path).write_text(json.dumps({
        "devices": {k: [[e.name, e.start, e.dur] for e in v]
                    for k, v in tr.devices.items()},
        "host": [[e.name, e.start, e.dur] for e in tr.host]}))


def load_json(path: str | Path) -> Trace:
    raw = json.loads(Path(path).read_text())
    return Trace(devices={k: [Event(*e) for e in v]
                          for k, v in raw["devices"].items()},
                 host=[Event(*e) for e in raw["host"]])


# ---------------------------------------------------------------------------
# Intervals
# ---------------------------------------------------------------------------

def _clip(events, lo, hi):
    for e in events:
        a, b = max(e.start, lo), min(e.end, hi)
        if b > a:
            yield a, b, e


def union(events, lo: float, hi: float) -> list[tuple[float, float]]:
    """The merged intervals in which at least one event runs."""
    out: list[list[float]] = []
    for a, b, _ in sorted(_clip(events, lo, hi)):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def busy_ns(events, lo: float, hi: float) -> float:
    return sum(b - a for a, b in union(events, lo, hi))


def op_ns(events, pattern: str, lo: float, hi: float) -> tuple[float, int]:
    """(summed duration inside the window, count) of the events whose name
    matches ``pattern`` (a regular expression, searched)."""
    rx = re.compile(pattern)
    hit = [(a, b) for a, b, e in _clip(events, lo, hi) if rx.search(e.name)]
    return sum(b - a for a, b in hit), len(hit)


def exposed_ns(events, lo: float, hi: float,
               collective: re.Pattern = COLLECTIVE) -> float:
    """Time in which a collective runs and no other operation does."""
    coll = [e for e in events if collective.search(e.name)]
    comp = [e for e in events if not collective.search(e.name)]
    busy = union(comp, lo, hi)
    total = 0.0
    for a, b in union(coll, lo, hi):
        covered = sum(max(0.0, min(b, y) - max(a, x)) for x, y in busy)
        total += (b - a) - covered
    return total


def gaps(events, lo: float, hi: float) -> list[tuple[float, float]]:
    """The idle intervals of a device inside the window."""
    out, t = [], lo
    for a, b in union(events, lo, hi):
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if hi > t:
        out.append((t, hi))
    return out


def _seconds(by_ns: dict, top: int) -> list[list]:
    return [[k, v * 1e-9] for k, v in
            sorted(by_ns.items(), key=lambda kv: -kv[1])][:top]


def _innermost(host, lo: float, hi: float) -> list[tuple[float, float, str]]:
    """The host timeline as pieces (start, end, name of the innermost span
    running then); spans of one thread nest, so the innermost is the
    running span that started last."""
    marks = sorted([(max(e.start, lo), 1, i) for i, e in enumerate(host)
                    if e.end > lo and e.start < hi] +
                   [(min(e.end, hi), 0, i) for i, e in enumerate(host)
                    if e.end > lo and e.start < hi])
    pieces, active, t = [], {}, lo
    for x, kind, i in marks:
        if x > t:
            name = (max(active.items(), key=lambda kv: kv[1])[0]
                    if active else None)
            pieces.append((t, x, host[name].name if name is not None
                           else "host: other"))
            t = x
        if kind:
            active[i] = host[i].start
        else:
            active.pop(i, None)
    if hi > t:
        pieces.append((t, hi, "host: other"))
    return pieces


def attribute_gaps(events, host, lo: float, hi: float,
                   top: int = 10) -> list[list]:
    """Idle time of a device summed by what the host was doing in it: the
    innermost benchmark span running at each idle instant, or
    ``host: other`` where none runs."""
    by: dict[str, float] = defaultdict(float)
    pieces = _innermost(host, lo, hi)
    j = 0
    for a, b in gaps(events, lo, hi):
        while j < len(pieces) and pieces[j][1] <= a:
            j += 1
        k = j
        while k < len(pieces) and pieces[k][0] < b:
            x, y, name = pieces[k]
            by[name] += min(b, y) - max(a, x)
            k += 1
    return _seconds(by, top)


def self_times(events, lo: float, hi: float) -> list[tuple[str, float]]:
    """(name, time inside the window not covered by a nested event) of each
    event: a loop's event encloses the events of its body, which would
    otherwise be counted twice."""
    out, stack = [], []          # stack: [name, end, self time]
    for a, b, e in sorted(_clip(events, lo, hi),
                          key=lambda x: (x[0], -(x[1] - x[0]))):
        while stack and stack[-1][1] <= a:
            out.append((stack[-1][0], stack[-1][2]))
            stack.pop()
        if stack:
            stack[-1][2] -= min(b, stack[-1][1]) - a
        stack.append([e.name, b, b - a])
    out += [(name, t) for name, _, t in stack]
    return out


def top_ops(events, lo: float, hi: float, top: int = 10) -> list[list]:
    """The operations that took most device time, by self time:
    [[name, seconds], ...]."""
    by: dict[str, float] = defaultdict(float)
    for name, t in self_times(events, lo, hi):
        by[name] += t
    return _seconds(by, top)

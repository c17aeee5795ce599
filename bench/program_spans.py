"""The program's own host spans in a traced run's profile.

The serving engine marks its loop with ``jax.profiler`` annotations named
``repro.*`` (``repro.serve.tick`` and its children; see
``repro/serve/engine.py``).  ``trace.load`` keeps the benchmark's ``bench.*``
spans only, so a reader of the program's spans reads them here, from the same
``.xplane.pb``: the newest under the run's trace directory, checked to be the
profile the run's ``ctx`` was reduced from (its one ``bench.window`` span is
``ctx["trace_window"]``).  A program without such spans gives none.
"""
from __future__ import annotations

from pathlib import Path

from bench import harness, trace

PREFIX = "repro."
TRACES = harness.ROOT / ".bench_trace"


def load(ctx: dict, root: str | Path | None = None) -> list[trace.Event]:
    """The ``repro.*`` host spans of the traced run, by start time, from
    the newest profile under ``root`` (default ``.bench_trace/``)."""
    from jax.profiler import ProfileData
    root = TRACES if root is None else root
    files = sorted(Path(root).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        raise harness.BenchError(f"no .xplane.pb under {root}")
    spans, windows = [], []
    for plane in ProfileData.from_file(str(files[-1])).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(PREFIX):
                    spans.append(trace.Event(e.name, e.start_ns,
                                             e.duration_ns))
                elif e.name == "bench.window":
                    windows.append(trace.Event(e.name, e.start_ns,
                                               e.duration_ns))
    if [(w.start, w.end) for w in windows] != [tuple(ctx["trace_window"])]:
        raise harness.BenchError(
            f"{files[-1]} is not the profile of this run: its bench.window "
            f"spans {[(w.start, w.end) for w in windows]}, the run's "
            f"{tuple(ctx['trace_window'])}")
    return sorted(spans, key=lambda e: e.start)

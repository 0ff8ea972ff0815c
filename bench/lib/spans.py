"""The program's own spans in a traced run, and the device's idle time
split by the program layer that holds it.

While a profiler records, `repro.obs.span` writes each span as a
`TraceAnnotation` named `f2.<name>`, with its args, onto the profile's
`/host:CPU` plane, on the clock of the device ops.  `Trace`
(`bench/lib/trace.py`) keeps only the benchmark's own `bench.*` host
events, so the program's are read here, from the profile the run leaves
in its run directory until its readers have run
(`<checkout>/.bench_run/trace`: `bench/run.py`'s `RUN_DIR`, and the
`trace` directory `bench/lib/cell.py` records into).  A profile whose
`bench.window` is not `ctx.trace`'s, another run's, is not read.

Each idle piece of the window (the complement of the first device's busy
union, as `device_idle_pct` counts it) goes to the innermost program
span that covers it, so a span's share is its self time in idle.  The
spans fall into layers, each span in exactly one, so the layers' shares
and the share under no program span sum to `device_idle_pct`:

    poll        f2.sessions.poll, .fetch, .collect
    enqueue     f2.sessions.enqueue
    compaction  f2.compact.* (the scheduler's bounds read and its passes)
    wal         f2.wal.*, f2.durability.* (the durability layer)
    step        every other program span: f2.sessions.step, .fold,
                f2.sharded.*, f2.rebalance.*, ... (the step's host part)
    outside     no program span: the client's own code
"""
from __future__ import annotations

import glob
import os
from dataclasses import dataclass
from functools import cached_property
from pathlib import Path
from typing import Dict, List, Optional, Tuple

from .trace import WINDOW, Event

PREFIX = "f2."
RUN_TRACE_DIR = Path(__file__).resolve().parents[2] / ".bench_run" / "trace"
POLL = {"sessions.poll", "sessions.fetch", "sessions.collect"}
PASSES = {"compact.hot_cold", "compact.cold_cold", "compact.single_log",
          "compact.chunk_gc"}


def layer_of(span: Optional[str]) -> str:
    """The layer of a program span's name (`f2.` included); `outside`
    for None."""
    if span is None:
        return "outside"
    name = span[len(PREFIX):]
    if name in POLL:
        return "poll"
    if name == "sessions.enqueue":
        return "enqueue"
    if name.startswith("compact."):
        return "compaction"
    if name.startswith(("wal.", "durability.")):
        return "wal"
    return "step"


@dataclass
class ProgramSpans:
    """The program's spans of one traced window, args in `stats`, and
    the window's idle intervals (ns)."""
    window: Tuple[int, int]
    spans: List[Event]
    idle: List[Tuple[int, int]]

    @classmethod
    def of(cls, trace, spans: List[Event]) -> "ProgramSpans":
        """With the window and the device's idle time of `trace` (a
        `Trace`)."""
        lo, hi = trace.window()
        devs = [d for d in trace.devices if trace.ops[d]]
        idle, cur = [], lo
        for s, e in (trace.busy(devs[0]) if devs else []):
            if s > cur:
                idle.append((cur, s))
            cur = max(cur, e)
        if cur < hi:
            idle.append((cur, hi))
        inside = [sp for sp in spans if sp.end > lo and sp.start < hi]
        return cls(window=(lo, hi), spans=inside, idle=idle)

    @cached_property
    def idle_by_span(self) -> Dict[Optional[str], float]:
        """Idle seconds by the innermost program span covering them (None:
        under no program span).  Innermost is the covering span that
        started last, the shorter one on a tie, so nested spans split
        exactly and the values sum to the window's idle time."""
        lo, hi = self.window
        spans = self.spans
        marks = sorted([(max(sp.start, lo), 1, i)
                        for i, sp in enumerate(spans)]
                       + [(min(sp.end, hi), 0, i)
                          for i, sp in enumerate(spans)])
        # the window cut into pieces, each under one innermost span or none
        pieces: List[Tuple[int, int, Optional[str]]] = []
        active: Dict[int, Event] = {}
        cur = lo
        for t, opens, i in marks + [(hi, 0, -1)]:
            if t > cur:
                inner = max(active.values(),
                            key=lambda sp: (sp.start, -sp.end),
                            default=None)
                pieces.append((cur, t, inner.name if inner else None))
                cur = t
            if opens:
                active[i] = spans[i]
            else:
                active.pop(i, None)
        tot: Dict[Optional[str], int] = {}
        j = 0
        for s, e in self.idle:
            while pieces[j][1] <= s:
                j += 1
            k = j
            while k < len(pieces) and pieces[k][0] < e:
                a, b, name = pieces[k]
                tot[name] = tot.get(name, 0) + min(b, e) - max(a, s)
                k += 1
        return {k: v * 1e-9 for k, v in tot.items()}


def read_profile(trace_dir) -> Tuple[List[Tuple[int, int]], List[Event]]:
    """The `bench.window` intervals and the program's spans in the one
    `.xplane.pb` under `trace_dir` (nothing where there is not one)."""
    paths = glob.glob(os.path.join(str(trace_dir), "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        return [], []
    from jax.profiler import ProfileData
    windows, spans = [], []
    for plane in ProfileData.from_file(paths[0]).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name == WINDOW:
                    windows.append((int(e.start_ns), int(e.end_ns)))
                elif e.name.startswith(PREFIX):
                    spans.append(Event(e.name, int(e.start_ns),
                                       int(e.end_ns), dict(e.stats)))
    return windows, spans


def program_spans(ctx) -> Optional[ProgramSpans]:
    """The program's spans of the run's traced window, read once per run
    (`ctx.program_spans`); None where the run was not traced, left no
    profile of this window, or the program wrote no span to it."""
    if not hasattr(ctx, "program_spans"):
        ctx.program_spans = None
        if ctx.trace is not None:
            windows, spans = read_profile(RUN_TRACE_DIR)
            if windows == [ctx.trace.window()] and spans:
                ctx.program_spans = ProgramSpans.of(ctx.trace, spans)
    ps = ctx.program_spans
    return ps if ps is not None and ps.spans else None


def idle_pct(ctx, layer: str) -> Optional[float]:
    """The share (%) of the traced window in which the device idled under
    `layer`; None where the window holds no program span of the layer
    (for `outside`: no program span at all)."""
    ps = program_spans(ctx)
    if ps is None or (layer != "outside" and not any(
            layer_of(sp.name) == layer for sp in ps.spans)):
        return None
    lo, hi = ps.window
    idle = sum(v for k, v in ps.idle_by_span.items()
               if layer_of(k) == layer)
    return 100.0 * idle / ((hi - lo) * 1e-9)


def mean_span_ms(ctx, names) -> Optional[float]:
    """Mean duration (ms) of the program spans named in `names` (without
    `f2.`) that start in the window and lie inside no other span of
    `names`; None where there is none."""
    ps = program_spans(ctx)
    if ps is None:
        return None
    lo, hi = ps.window
    sel = sorted((sp for sp in ps.spans if sp.name[len(PREFIX):] in names),
                 key=lambda sp: (sp.start, -sp.end))
    top, reach = [], None
    for sp in sel:
        if reach is not None and sp.end <= reach:
            continue                    # inside an earlier span of `names`
        reach = sp.end
        if lo <= sp.start < hi:
            top.append(sp)
    if not top:
        return None
    return 1e-6 * sum(sp.end - sp.start for sp in top) / len(top)

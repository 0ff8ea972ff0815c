"""Reduce a JAX profiler trace to the numbers the per-layer metrics read.

The profiler writes an `.xplane.pb`; `jax.profiler.ProfileData` reads it.
Device planes are named `/device:TPU:<n>`.  Their `XLA Modules` line holds
one event per executed program, named after the jitted function
(`jit_routed_step(<hash>)`); their `XLA Ops` line one event per executed
HLO instruction, named by the instruction's HLO text
(`%fusion.38 = s32[...] fusion(...), kind=...`; a Pallas kernel is a
`custom-call` with `custom_call_target="tpu_custom_call"`, and carries no
kernel name).  An op belongs to the program whose event covers its start.
The host plane `/host:CPU` holds the benchmark's own `TraceAnnotation`
spans (`bench.*`) on the same clock.

Each op event keeps a short name (`<instruction> <opcode>`), its program
(`stats["module"]`, without the hash) and, for custom calls, the whole
HLO text (`stats["hlo"]`), from which a reader can tell kernels apart by
their operands.

Everything is reduced inside the traced window, the span of the
`bench.window` annotation.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

WINDOW = "bench.window"
HOST_PREFIX = "bench."


@dataclass
class Event:
    name: str
    start: int              # ns
    end: int                # ns
    stats: Dict[str, object] = field(default_factory=dict)


def _union(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[Tuple[int, int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def _clip(events: List[Event], lo: int, hi: int) -> List[Tuple[int, int]]:
    return [(max(e.start, lo), min(e.end, hi)) for e in events
            if e.end > lo and e.start < hi]


def _module_name(name: str) -> str:
    """`jit_routed_step(1044...)` -> `jit_routed_step`."""
    return name.split("(", 1)[0]


_OPCODE = re.compile(r" ([a-z][a-z0-9_\-]*)\(")


def _op_event(e, modules: List[Event], starts: List[int]) -> Event:
    text = e.name
    inst, _, rest = text.partition(" = ")
    m = _OPCODE.search(rest)
    short = f"{inst.lstrip('%')} {m.group(1) if m else '?'}"
    start = int(e.start_ns)
    i = bisect.bisect_right(starts, start) - 1
    mod = modules[i].name if i >= 0 and modules[i].end >= start else "?"
    stats = {"module": mod}
    if "custom-call(" in rest:
        stats["hlo"] = text
    return Event(short, start, int(e.end_ns), stats)


@dataclass
class Trace:
    """Device op and module events per device, and the host spans."""
    ops: Dict[str, List[Event]]        # device plane name -> XLA Ops
    modules: Dict[str, List[Event]]    # device plane name -> XLA Modules
    host: List[Event]                  # bench.* annotations

    @classmethod
    def from_dir(cls, trace_dir: str) -> "Trace":
        paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                          recursive=True)
        if len(paths) != 1:
            raise RuntimeError(f"expected one xplane file under "
                               f"{trace_dir}, found {paths}")
        return cls.from_file(paths[0])

    @classmethod
    def from_file(cls, path: str) -> "Trace":
        from jax.profiler import ProfileData
        return cls.from_profile(ProfileData.from_file(path))

    @classmethod
    def from_bytes(cls, data: bytes) -> "Trace":
        """From the bytes of a serialized `.xplane.pb`."""
        from jax.profiler import ProfileData
        return cls.from_profile(ProfileData.from_serialized_xspace(data))

    @classmethod
    def from_profile(cls, pd) -> "Trace":
        ops: Dict[str, List[Event]] = {}
        modules: Dict[str, List[Event]] = {}
        host: List[Event] = []
        for plane in pd.planes:
            if re.fullmatch(r"/device:TPU:\d+", plane.name):
                lines = {ln.name: ln for ln in plane.lines}
                mods = sorted(
                    (Event(_module_name(e.name), int(e.start_ns),
                           int(e.end_ns))
                     for e in lines["XLA Modules"].events)
                    if "XLA Modules" in lines else [],
                    key=lambda e: e.start)
                modules[plane.name] = mods
                starts = [m.start for m in mods]
                ops[plane.name] = [
                    _op_event(e, mods, starts)
                    for e in lines["XLA Ops"].events] \
                    if "XLA Ops" in lines else []
            elif plane.name == "/host:CPU":
                for line in plane.lines:
                    host.extend(
                        Event(e.name, int(e.start_ns), int(e.end_ns))
                        for e in line.events
                        if e.name.startswith(HOST_PREFIX))
        return cls(ops=ops, modules=modules, host=host)

    # -- the window ----------------------------------------------------------
    def window(self) -> Tuple[int, int]:
        w = [e for e in self.host if e.name == WINDOW]
        if len(w) != 1:
            raise RuntimeError(f"expected one {WINDOW} span, found {len(w)}")
        return w[0].start, w[0].end

    def window_s(self) -> float:
        lo, hi = self.window()
        return (hi - lo) * 1e-9

    @property
    def devices(self) -> List[str]:
        return sorted(self.ops)

    # -- device time ---------------------------------------------------------
    def busy(self, device: str) -> List[Tuple[int, int]]:
        """Union of the intervals in which an op ran, inside the window."""
        lo, hi = self.window()
        return _union(_clip(self.ops.get(device, []), lo, hi))

    def busy_s(self) -> float:
        """Busy seconds, averaged over the devices that ran anything."""
        devs = [d for d in self.devices if self.ops[d]]
        if not devs:
            return 0.0
        tot = sum(sum(e - s for s, e in self.busy(d)) for d in devs)
        return tot * 1e-9 / len(devs)

    def module_s(self, pattern: str) -> float:
        """Device seconds of programs whose name matches `pattern` (a
        regular expression searched in the module name), inside the
        window, summed over devices."""
        lo, hi = self.window()
        rx = re.compile(pattern)
        return sum(e - s for evs in self.modules.values()
                   for s, e in _clip([x for x in evs if rx.search(x.name)],
                                     lo, hi)) * 1e-9

    def module_count(self, pattern: str) -> int:
        lo, hi = self.window()
        rx = re.compile(pattern)
        return sum(1 for evs in self.modules.values() for x in evs
                   if rx.search(x.name) and lo <= x.start < hi)

    def op_s(self, op, module_pattern: str = "") -> float:
        """Device seconds of ops run inside a program whose name matches
        `module_pattern`, and that `op` selects: a regular expression
        searched in the op's HLO text (custom calls) or short name, or a
        predicate on the event."""
        lo, hi = self.window()
        rmod = re.compile(module_pattern)
        if callable(op):
            pick = op
        else:
            rop = re.compile(op)
            pick = lambda x: rop.search(str(x.stats.get("hlo", x.name)))
        sel = [x for evs in self.ops.values() for x in evs
               if rmod.search(str(x.stats.get("module", ""))) and pick(x)]
        return sum(e - s for s, e in _clip(sel, lo, hi)) * 1e-9

    # -- the breakdown -------------------------------------------------------
    def top_ops(self, n: int = 10) -> List[list]:
        """The device ops that took most time: [[module/op, seconds]]."""
        lo, hi = self.window()
        tot: Dict[str, int] = {}
        for evs in self.ops.values():
            for x in evs:
                if x.end > lo and x.start < hi:
                    key = f"{x.stats.get('module', '?')}/{x.name}"
                    tot[key] = tot.get(key, 0) + (min(x.end, hi)
                                                  - max(x.start, lo))
        top = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
        return [[k, v * 1e-9] for k, v in top]

    def idle_gaps(self, n: int = 10) -> List[list]:
        """Idle time of the first device by what the host was doing: each
        gap between device ops goes to the innermost `bench.*` span that
        covers its middle ("untraced" where none does)."""
        devs = [d for d in self.devices if self.ops[d]]
        lo, hi = self.window()
        if not devs:
            return [["untraced", (hi - lo) * 1e-9]]
        busy = self.busy(devs[0])
        gaps, cur = [], lo
        for s, e in busy:
            if s > cur:
                gaps.append((cur, s))
            cur = max(cur, e)
        if cur < hi:
            gaps.append((cur, hi))
        spans = sorted((e for e in self.host if e.name != WINDOW),
                       key=lambda e: e.start)
        starts = [sp.start for sp in spans]
        tot: Dict[str, int] = {}
        for s, e in gaps:
            mid = (s + e) // 2
            inner: Optional[Event] = None
            # the benchmark's spans inside the window do not nest, so the
            # span covering `mid` is among the last few starting before it
            i = bisect.bisect_right(starts, mid)
            for sp in spans[max(0, i - 4):i]:
                if sp.end >= mid and (inner is None
                                      or sp.end - sp.start
                                      < inner.end - inner.start):
                    inner = sp
            name = inner.name if inner is not None else "untraced"
            tot[name] = tot.get(name, 0) + (e - s)
        top = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
        return [[k, v * 1e-9] for k, v in top]


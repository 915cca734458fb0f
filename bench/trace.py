"""Reduce a profiler trace (``.xplane.pb``) to what the per-layer metrics
read: device operation intervals, busy time as their union, idle gaps,
kernel time by instruction name, and the harness's own host annotations.

The trace is read with ``jax.profiler.ProfileData``. Device planes are the
planes named ``/device:TPU:<n>`` (or GPU); their operations are the events
of the line ``XLA Ops``. Host annotations are events of the host plane
whose name starts with ``bench/``.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Optional

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):(\d+)$")
OPS_LINE = "XLA Ops"


@dataclasses.dataclass
class Event:
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


@dataclasses.dataclass
class Trace:
    """``ops[device] -> [Event]`` and ``marks -> [Event]`` (host
    annotations), all on the trace's clock."""
    ops: dict
    marks: list

    @property
    def devices(self) -> list:
        return sorted(self.ops)


def latest_xplane(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def load(path: str) -> Trace:
    import jax
    data = jax.profiler.ProfileData.from_file(path)
    return from_planes((p.name, [(ln.name, [(e.name, e.start_ns,
                                             e.duration_ns)
                                            for e in ln.events])
                                 for ln in p.lines])
                       for p in data.planes)


def from_planes(planes) -> Trace:
    """``planes``: iterable of ``(plane name, [(line name, [(event name,
    start ns, duration ns)])])``."""
    ops, marks = {}, []
    for pname, lines in planes:
        m = DEVICE_PLANE.match(pname)
        for lname, events in lines:
            if m and lname == OPS_LINE:
                ops.setdefault(int(m.group(2)), []).extend(
                    Event(n, float(s), float(d)) for n, s, d in events)
            elif pname.startswith("/host"):
                marks.extend(Event(n, float(s), float(d))
                             for n, s, d in events if n.startswith("bench/"))
    for evs in ops.values():
        evs.sort(key=lambda e: e.start_ns)
    marks.sort(key=lambda e: e.start_ns)
    return Trace(ops=ops, marks=marks)


def union(events, lo: float, hi: float) -> list:
    """Merged ``[(start, end)]`` of the events, clipped to ``[lo, hi]``."""
    out = []
    for e in sorted(events, key=lambda e: e.start_ns):
        s, t = max(e.start_ns, lo), min(e.end_ns, hi)
        if t <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], t)
        else:
            out.append([s, t])
    return [tuple(iv) for iv in out]


def busy_ns(events, lo: float, hi: float) -> float:
    return sum(t - s for s, t in union(events, lo, hi))


def gaps(events, lo: float, hi: float) -> list:
    """Idle ``[(start, end)]`` of the window ``[lo, hi]``."""
    out, cur = [], lo
    for s, t in union(events, lo, hi):
        if s > cur:
            out.append((cur, s))
        cur = max(cur, t)
    if hi > cur:
        out.append((cur, hi))
    return out


def window(trace: Trace, mark: str = "bench/round") -> Optional[tuple]:
    """The span of the harness's annotations named ``mark``."""
    ms = [m for m in trace.marks if m.name == mark]
    if not ms:
        return None
    return ms[0].start_ns, max(m.end_ns for m in ms)


def op_name(event_name: str) -> str:
    """The HLO instruction an op event runs: the trace names an event by
    the instruction's text, ``%fusion.12 = f32[...] fusion(...)``."""
    return event_name.split(" = ", 1)[0].strip().lstrip("%")


def short_name(event_name: str) -> str:
    """Instruction name and result shape, for a readable breakdown."""
    name, _, rest = event_name.partition(" = ")
    shape = rest.split("{", 1)[0].split(" ", 1)[0] if rest else ""
    return f"{name.strip().lstrip('%')} {shape}".strip()[:96]


def is_container(event_name: str) -> bool:
    """A loop or call whose body's ops are events of their own."""
    return re.match(r"(while|conditional|call)(\.|$)",
                    op_name(event_name)) is not None


def time_by_name(events, pattern) -> tuple:
    """(count, total duration ns) of the events whose instruction name
    matches ``pattern``."""
    rx = re.compile(pattern) if isinstance(pattern, str) else pattern
    hits = [e.dur_ns for e in events if rx.search(op_name(e.name))]
    return len(hits), float(sum(hits))


def top_ops(events, n: int = 10) -> list:
    """``[[name, seconds]]`` of the ``n`` operations that took most time
    (loops and calls left out: their bodies' ops are counted)."""
    tot = {}
    for e in events:
        if is_container(e.name):
            continue
        k = short_name(e.name)
        tot[k] = tot.get(k, 0.0) + e.dur_ns
    return [[k, v * 1e-9] for k, v in sorted(tot.items(),
                                             key=lambda kv: -kv[1])[:n]]


def label_gaps(idle: list, spans: list, n: int = 10) -> list:
    """``[[host activity, seconds]]``: idle device time summed by the
    innermost host span (``(name, start ns, end ns)``, on the trace clock)
    that covers each gap's midpoint, longest first; ``between rounds``
    where none does."""
    tot = {}
    for s, t in idle:
        mid = 0.5 * (s + t)
        best = None
        for name, a, b in spans:
            if a <= mid <= b and (best is None or b - a < best[2] - best[1]):
                best = (name, a, b)
        key = best[0] if best else "between rounds"
        tot[key] = tot.get(key, 0.0) + (t - s)
    return [[k, v * 1e-9] for k, v in sorted(tot.items(),
                                             key=lambda kv: -kv[1])[:n]]

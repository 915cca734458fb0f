"""Nestable phase timers with correct device fencing.

jax dispatch is asynchronous: ``fn(x)`` returns as soon as the work is
*queued*, so ``time.perf_counter()`` around a call measures dispatch, not
execution — the exact bug the deployment bench shipped with and the
"65 ms noise windows" of the PR-4 log. Every timer here is explicit about
where the fence sits:

* ``Timeline.span("round/execute")`` — a nestable phase timer on the
  monotonic clock. Inside a span, ``sp.fence(value)`` blocks until
  ``value``'s device buffers are ready and books the wait into the span's
  ``sync_s``; the emitted event carries ``dur_s`` (wall) and ``sync_s``
  (device wait) separately, so host cost = ``dur_s - sync_s``.
* ``time_fenced(fn, repeats=N)`` — the bench primitive: dispatch ``fn``
  ``N`` times back-to-back, block ONCE on the last result, return wall
  seconds. This is the async-dispatch methodology every engine bench uses
  (a per-call fence would serialize dispatch against compute).
* ``fenced(fn)`` — call once, block on the result, return
  ``(out, wall_s)``. For host-side work (numpy) the fence is a no-op.

Spans nest lexically: the timeline keeps a stack, and every event records
its full ``path`` ("run/round/execute") plus ``depth``, so a reader can
rebuild the tree without matching ids. Disabled timelines hand out one
shared null span — entering it is a branch and two no-op calls.

An enabled span is also a host annotation of the profiler's trace
(``jax.profiler.TraceAnnotation``, named by the span, with ``round=`` where
the span has it; the ``round`` span is a ``StepTraceAnnotation``, so the
trace gets its steps). A profile (``ObsConfig(profile_rounds=...)``) thus
shows the program's spans on the device trace's clock. With no profiler
recording, the annotation costs a check and two calls.
"""
from __future__ import annotations

import time
from typing import Any, Callable, Optional


def _block(value: Any) -> Any:
    """Block until every jax buffer in ``value`` is ready. Non-jax leaves
    (numpy arrays, floats, configs) pass through untouched."""
    import jax
    try:
        return jax.block_until_ready(value)
    except Exception:
        # jax.block_until_ready tree-maps; exotic leaves that object are
        # host values and already "ready"
        return value


def fenced(fn: Callable[[], Any]) -> tuple[Any, float]:
    """``(out, wall_s)`` of one fenced call: dispatch + device execute,
    never dispatch alone."""
    t0 = time.perf_counter()
    out = fn()
    _block(out)
    return out, time.perf_counter() - t0


def time_fenced(fn: Callable[[], Any], repeats: int = 1) -> float:
    """Wall seconds of ``repeats`` back-to-back dispatches of ``fn`` with
    ONE fence on the final result — the throughput-bench clock (queue the
    whole window, block at the end)."""
    out = None
    t0 = time.perf_counter()
    for _ in range(repeats):
        out = fn()
    _block(out)
    return time.perf_counter() - t0


class _NullSpan:
    """Shared do-nothing span for disabled timelines."""
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def fence(self, value):
        return value

    def note(self, **fields):
        return None


NULL_SPAN = _NullSpan()


def _annotation(name: str, fields: dict):
    """The profiler annotation of one span (see the module docstring)."""
    import jax.profiler
    r = fields.get("round")
    if r is None:
        return jax.profiler.TraceAnnotation(name)
    if name == "round":
        return jax.profiler.StepTraceAnnotation(name, step_num=r, round=r)
    return jax.profiler.TraceAnnotation(name, round=r)


class Span:
    """One live phase. Use as a context manager via ``Timeline.span``.

    Names may be hierarchical ("round/execute"); the emitted ``path``
    splices them into the enclosing stack without duplicating shared
    segments, so ``span("round")`` containing ``span("round/execute")``
    yields the path ``.../round/execute``, not ``.../round/round/execute``.
    """
    __slots__ = ("_tl", "name", "fields", "t_start", "sync_s", "_extra",
                 "_pushed", "_depth", "_ann")

    def __init__(self, tl: "Timeline", name: str, fields: dict):
        self._tl = tl
        self.name = name
        self.fields = fields
        self.sync_s = 0.0
        self._extra: Optional[dict] = None

    def __enter__(self):
        tl = self._tl
        stack = tl._stack
        segs = self.name.split("/")
        # drop the longest prefix of this name that repeats the stack tail
        k = 0
        for i in range(min(len(segs), len(stack)), 0, -1):
            if stack[len(stack) - i:] == segs[:i]:
                k = i
                break
        if k == len(segs):        # name identical to the stack tail: still
            k = len(segs) - 1     # push the leaf so pop stays balanced
        self._pushed = len(segs) - k
        stack.extend(segs[k:])
        self._depth = tl._open
        tl._open += 1
        self._ann = _annotation(self.name, self.fields)
        self._ann.__enter__()
        self.t_start = time.perf_counter()
        return self

    def fence(self, value):
        """Block until ``value`` is device-ready; the wait books into this
        span's ``sync_s`` (device time the host spent waiting)."""
        t0 = time.perf_counter()
        _block(value)
        self.sync_s += time.perf_counter() - t0
        return value

    def note(self, **fields):
        """Attach extra fields to the span's emitted event."""
        if self._extra is None:
            self._extra = {}
        self._extra.update(fields)

    def __exit__(self, *exc):
        t_end = time.perf_counter()
        self._ann.__exit__(*exc)
        tl = self._tl
        stack = tl._stack
        path = "/".join(stack)
        del stack[len(stack) - self._pushed:]
        tl._open -= 1
        event = {
            "ev": "span",
            "name": self.name,
            "path": path,
            "depth": self._depth,
            "t": round(self.t_start - tl.t0, 6),
            "dur_s": round(t_end - self.t_start, 6),
            "sync_s": round(self.sync_s, 6),
        }
        if self.fields:
            event.update(self.fields)
        if self._extra:
            event.update(self._extra)
        tl._sink.emit(event)
        return False


class Timeline:
    """Nestable span timers writing one event per closed span to a sink."""

    def __init__(self, sink, enabled: bool = True):
        self._sink = sink
        self.enabled = enabled
        self._stack: list[str] = []   # path segments of the open spans
        self._open = 0                # count of open spans (event depth)
        self.t0 = time.perf_counter()

    def span(self, name: str, **fields) -> Any:
        """``with tl.span("round/execute"): ...`` — disabled timelines
        return the shared null span (branch-only cost)."""
        if not self.enabled:
            return NULL_SPAN
        return Span(self, name, fields)

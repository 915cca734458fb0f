"""Run-wide gauges: recompiles, pytree/engine-state bytes, host RSS.

Everything here is zero-dependency and survives being unavailable: the
recompile counter hooks jax's internal monitoring events but degrades to
``available=False`` if the private module moves; RSS reads ``/proc`` and
falls back to ``resource``.

The recompile counter answers the question ``RoundRecord`` can't: did XLA
silently recompile a round mid-run (a shape change, a new donation
pattern, a cache miss)? ``jax._src.monitoring`` fires one
``BACKEND_COMPILE_EVENT`` duration event per backend compile; counting
them between two snapshots counts compiles in that window — steady-state
rounds must show a delta of 0.
"""
from __future__ import annotations

import os
from typing import Optional


def pytree_bytes(tree) -> int:
    """Total array bytes across a pytree's leaves (device or numpy) — the
    PR-6 O(cohort) engine-state pin, hoisted so benches, gauges and tests
    share one definition. Non-array leaves (ints, configs) count 0."""
    import jax
    return int(sum(x.size * x.dtype.itemsize
                   for x in jax.tree_util.tree_leaves(tree)
                   if hasattr(x, "dtype") and hasattr(x, "size")))


def host_rss_bytes() -> int:
    """Current resident set size of this process, in bytes (0 if neither
    /proc nor the resource module can say)."""
    try:
        with open("/proc/self/statm") as f:
            pages = int(f.read().split()[1])
        return pages * os.sysconf("SC_PAGE_SIZE")
    except Exception:
        pass
    try:
        import resource
        return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024
    except Exception:
        return 0


class RecompileCounter:
    """Counts XLA backend compiles (and seconds spent in them) via jax's
    monitoring events. ``install()`` registers the listener; snapshot with
    ``.count`` / ``.duration_s``; window deltas via ``snapshot()``.

    One module-level counter (``global_counter()``) is shared by every Obs
    instance so repeated runs never stack listeners; unit tests may build
    their own and ``uninstall()`` it.
    """

    def __init__(self):
        self.count = 0
        self.duration_s = 0.0
        self.available = False
        self._installed = False
        self._event: Optional[str] = None

    def install(self) -> "RecompileCounter":
        if self._installed:
            return self
        try:
            from jax._src import monitoring
            from jax._src.dispatch import BACKEND_COMPILE_EVENT
        except Exception:          # toolchain moved the private hook
            self.available = False
            return self
        self._event = BACKEND_COMPILE_EVENT
        monitoring.register_event_duration_secs_listener(self._listen)
        self.available = True
        self._installed = True
        return self

    def _listen(self, event: str, duration: float, **kwargs) -> None:
        if event == self._event:
            self.count += 1
            self.duration_s += duration

    def uninstall(self) -> None:
        if not self._installed:
            return
        from jax._src import monitoring
        monitoring.unregister_event_duration_listener(self._listen)
        self._installed = False
        self.available = False

    def snapshot(self) -> tuple[int, float]:
        """(count, duration_s) so far — subtract two snapshots for a
        window delta."""
        return self.count, self.duration_s


_GLOBAL: Optional[RecompileCounter] = None


def global_counter() -> RecompileCounter:
    """The process-wide recompile counter, installed on first use."""
    global _GLOBAL
    if _GLOBAL is None:
        _GLOBAL = RecompileCounter().install()
    return _GLOBAL

"""Opt-in ``jax.profiler`` trace capture scoped to rounds N..M of a run.

``ObsConfig(profile_rounds=(2, 4))`` arms a capture that starts when round
2 begins and stops after round 4 ends; the trace lands in
``<run_dir>/profile/`` (open with TensorBoard's profile plugin or
Perfetto). The status lands in the manifest. A capture the caller asked for
that fails to start or stop raises: a run that was meant to be traced never
ends as if it had been.
"""
from __future__ import annotations

import os
from typing import Optional, Tuple


class ProfilerCapture:
    """Start/stop ``jax.profiler`` around a contiguous round window."""

    def __init__(self, rounds: Optional[Tuple[int, int]], out_dir: str):
        self.rounds = tuple(rounds) if rounds is not None else None
        if self.rounds is not None and self.rounds[0] > self.rounds[1]:
            raise ValueError(f"profile_rounds=(start, stop) needs start <= "
                             f"stop, got {self.rounds}")
        self.out_dir = out_dir
        self.active = False
        self.status = "off" if self.rounds is None else "armed"

    def round_started(self, round_index: int) -> None:
        if (self.rounds is None or self.active
                or round_index != self.rounds[0]):
            return
        import jax.profiler
        os.makedirs(self.out_dir, exist_ok=True)
        jax.profiler.start_trace(self.out_dir)
        self.active = True
        self.status = f"tracing rounds {self.rounds[0]}..{self.rounds[1]}"

    def round_finished(self, round_index: int) -> None:
        if self.active and round_index >= self.rounds[1]:
            self._stop()

    def close(self) -> None:
        """Stop a still-open capture (a run shorter than the window)."""
        if self.active:
            self._stop()

    def _stop(self) -> None:
        import jax.profiler
        self.active = False
        jax.profiler.stop_trace()
        self.status = f"captured -> {self.out_dir}"

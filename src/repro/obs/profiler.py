"""Opt-in ``jax.profiler`` trace capture scoped to rounds N..M of a run.

``ObsConfig(profile_rounds=(2, 4))`` arms a capture that starts when round
2 begins and stops after round 4 ends; the trace lands in
``<run_dir>/profile/`` (open with TensorBoard's profile plugin or
Perfetto). The status lands in the manifest. A capture the caller asked for
that fails to start or stop raises: a run that was meant to be traced never
ends as if it had been.

A TPU trace names each device op by its HLO instruction (``%fusion.12 =
...``) and carries none of its metadata, so the program's named scopes
(``core.split``'s ``sl/client``, ``sl/link``, ``sl/server``, ``fl/client``;
``kernels.attn.flash``'s ``flash_bwd``) are not on it. ``hlo_op_scopes``
reads them from the compiled module's text instead; an enabled run writes
that map for its round (``Obs.op_scopes``) beside the trace.
"""
from __future__ import annotations

import os
import re
from typing import Optional, Tuple

_COMPUTATION = re.compile(r"^(?:ENTRY )?%(\S+) .*\{$")
_INSTRUCTION = re.compile(r"^(?:ROOT )?%(\S+) = ")
_OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
_FUSION_CALLS = re.compile(r" fusion\(.*calls=%([^\s,]+)")
_REDUCER = re.compile(r"to_apply=%([^\s,]+)")


def hlo_op_scopes(hlo_text: str) -> tuple[str, dict]:
    """``(module name, {instruction: op_name})`` of a compiled module's HLO
    text, for every instruction that runs as an op of its own (fusion
    bodies and reducers left out). A fusion without metadata takes its
    root's: a fused op is charged to its root op's scope."""
    lines = hlo_text.splitlines()
    module = lines[0].split()[1].rstrip(",") if lines else ""
    comps, roots, fused = {}, {}, set()
    cname = None
    for line in lines:
        line = line.strip()
        m = _INSTRUCTION.match(line)
        if m is None:
            m = _COMPUTATION.match(line)
            if m:
                cname = m.group(1)
                comps[cname] = {}
            continue
        if cname is None:
            continue
        op = _OP_NAME.search(line)
        op = op.group(1) if op else None
        calls = _FUSION_CALLS.search(line)
        if calls:
            fused.add(calls.group(1))
        reducer = _REDUCER.search(line)
        if reducer and " call(" not in line:
            fused.add(reducer.group(1))
        comps[cname][m.group(1)] = (op, calls.group(1) if calls else None)
        if line.startswith("ROOT "):
            roots[cname] = op
    ops = {}
    for cname, instrs in comps.items():
        if cname in fused:
            continue
        for name, (op, calls) in instrs.items():
            op = op if op is not None else roots.get(calls)
            if op is not None:
                ops[name] = op
    return module, ops


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

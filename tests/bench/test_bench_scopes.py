"""Device time by the program's named scopes and its set-up spans, read
from a small trace and the op map and events a run with the program's
telemetry on leaves beside it; and each new reader silent where the
program or the trace gives it nothing to read."""
import json
import os
import shutil
from types import SimpleNamespace

import pytest

from bench import run, scopes
from bench import trace as trace_mod
from benchtools import BENCH

US = 1000.0   # ns
CELL = "tiny_cell"
READERS = ("server_ms.tokens", "client_ms.tokens", "attn_bwd_ms.tokens",
           "compile_flops_s")
OPS = {
    "fusion.1": "jit(sl_round)/while/body/sl/client/tanh",
    "fusion.2": "jit(sl_round)/while/body/sl/server/transpose(sl/server)/"
                "jvp(flash_bwd)/dot_general",
    "flash_attention_fwd.3": "jit(sl_round)/while/body/sl/server/"
                             "jvp(flash_attention_fwd)/pallas_call",
    "fusion.5": "jit(sl_round)/reduce_sum",
}
SPANS = [{"ev": "span", "name": "compile/flops", "dur_s": 2.5},
         {"ev": "span", "name": "compile/lower", "dur_s": 1.0},
         {"ev": "span", "name": "compile", "dur_s": 9.0},
         {"ev": "gauge", "round": 3},
         {"ev": "span", "name": "round", "round": 3, "dur_s": 0.6}]


def small_trace():
    """Two rounds on chip 0 (the busier), one op on chip 1."""
    ops = [("%while.7 = (s32[], f32[8]) while(%t)", 0 * US, 70 * US),
           ("%fusion.1 = f32[8]{0} fusion(%a)", 0 * US, 10 * US),
           ("%fusion.2 = f32[8]{0} fusion(%b)", 10 * US, 30 * US),
           ("%flash_attention_fwd.3 = f32[8]{0} custom-call(%c)", 40 * US,
            20 * US),
           ("%copy.4 = f32[8]{0} copy(%d)", 60 * US, 5 * US),
           ("%fusion.5 = f32[] fusion(%e)", 65 * US, 5 * US)]
    return trace_mod.from_planes([
        ("/device:TPU:0", [("XLA Ops", ops)]),
        ("/device:TPU:1", [("XLA Ops", [
            ("%fusion.1 = f32[8]{0} fusion(%a)", 0 * US, 5 * US)])]),
        ("/host:CPU", [("python", [("bench/round", 0, 50 * US),
                                   ("bench/round", 50 * US, 50 * US)])]),
    ])


class Ctx(run.Context):
    """What a reader sees of one traced run, without running one."""

    def __init__(self, trace=None, chips=2):
        self.cell = SimpleNamespace(name=CELL)
        self.chips, self.trace, self.spans = chips, trace, []
        self.traced_rounds = 2 if trace is not None else 0
        self.trace_window = (trace_mod.window(trace) if trace is not None
                             else None)


@pytest.fixture
def bench_dir(tmp_path):
    """A bench directory with the repo's readers and, optionally, what the
    program's telemetry wrote in the cell's run."""
    d = tmp_path / "bench"
    shutil.copytree(os.path.join(BENCH, "metrics"), d / "metrics")

    def with_run(ops=True, spans=True):
        rd = d / ".out" / "runs" / CELL
        if ops:
            os.makedirs(rd / "ops")
            (rd / "ops" / "jit_sl_round.json").write_text(json.dumps(
                {"module": "jit_sl_round", "ops": OPS}))
        if spans:
            os.makedirs(rd, exist_ok=True)
            (rd / "events.jsonl").write_text(
                "".join(json.dumps(e) + "\n" for e in SPANS))
        return str(d)
    return with_run


def reader_file(bench):
    return os.path.join(bench, "metrics", "server_ms.py")


def test_scope_time_per_round_on_the_busiest_chip(bench_dir):
    bench, ctx = bench_dir(), Ctx(small_trace())
    f = reader_file(bench)
    assert scopes.scope_ns_per_round(ctx, "sl/client", f) == 5 * US
    # forward kernel and backward fusion both ran in the server tier
    assert scopes.scope_ns_per_round(ctx, "sl/server", f) == 25 * US
    assert scopes.scope_ns_per_round(ctx, "flash_bwd", f) == 15 * US
    assert scopes.scope_ns_per_round(ctx, "sl/link", f) == 0.0


def test_breakdown_skips_loops_and_reports_unscoped(bench_dir):
    b = scopes.breakdown(Ctx(small_trace()), reader_file(bench_dir()))
    # the loop's own event is not counted; an op the map does not name
    # (copy.4) and one under no tier scope (fusion.5) are unscoped
    tiers = {"sl/client": 5e-6, "sl/link": 0.0, "sl/server": 25e-6,
             "fl/client": 0.0, "unscoped": 5e-6}
    assert b["scopes_s"] == pytest.approx({**tiers, "flash_bwd": 15e-6})
    assert b["ops_s"] == pytest.approx(35e-6)
    assert b["busy_s"] == pytest.approx(35e-6)
    # the tiers part the ops; the attention backward nests in the server's
    assert sum(tiers.values()) == pytest.approx(b["ops_s"])
    assert b["top"]["flash_bwd"] == [["fusion.2 f32[8]", pytest.approx(15e-6)]]
    assert [k for k, _ in b["top"]["sl/server"]] == [
        "fusion.2 f32[8]", "flash_attention_fwd.3 f32[8]"]
    assert [k for k, _ in b["top"]["unscoped"]] == ["copy.4 f32[8]",
                                                    "fusion.5 f32[]"]


def test_setup_spans_are_those_without_a_round(bench_dir):
    spans = scopes.setup_spans(Ctx(), reader_file(bench_dir()))
    assert [e["name"] for e in spans] == ["compile/flops", "compile/lower",
                                          "compile"]


def test_readers_read_scopes_and_setup_spans(bench_dir, capsys):
    bench = bench_dir()
    got = {m: run.load_reader(m, bench)(Ctx(small_trace())) for m in READERS}
    assert got == pytest.approx({
        "server_ms.tokens": 0.025, "client_ms.tokens": 0.005,
        "attn_bwd_ms.tokens": 0.015, "compile_flops_s": 2.5})
    assert f"[{CELL}] scopes per round:" in capsys.readouterr().err


@pytest.mark.parametrize("name", READERS[:3])
@pytest.mark.parametrize("case", ["no_trace", "no_op_map"])
def test_device_readers_return_none_without_what_they_read(bench_dir, name,
                                                           case):
    """A CPU run has no device trace; the parent program writes no op
    map."""
    if case == "no_trace":
        bench, ctx = bench_dir(), Ctx()
    else:
        bench, ctx = bench_dir(ops=False), Ctx(small_trace())
    assert run.load_reader(name, bench)(ctx) is None


@pytest.mark.parametrize("case", ["no_run", "no_flops_span"])
def test_compile_flops_returns_none_without_its_span(bench_dir, case):
    """A run without the program's telemetry leaves no events; a program
    without the span leaves none of it."""
    if case == "no_run":
        bench = bench_dir(ops=False, spans=False)
    else:
        bench = bench_dir(spans=False)
        rd = os.path.join(bench, ".out", "runs", CELL, "events.jsonl")
        with open(rd, "w") as f:
            f.write(json.dumps(SPANS[2]) + "\n")
    assert run.load_reader("compile_flops_s", bench)(Ctx()) is None

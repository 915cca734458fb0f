"""The harness end to end on the CPU at a tiny size: the program's first
rounds through ``Plan.run_round`` against the plain reference, with the
int8 link kernel in interpret mode, and every number within the tiny
cells' limits."""
import time

from bench import run
from benchtools import INT8_LIMITS


def _run(root, cell, trace=False):
    return run.run_cell(cell, 2 ** 31 + 11, 0.5, trace,
                        t_start=time.perf_counter(), allow_cpu=True,
                        root=root)


def test_split_cnn_matches_reference(tiny_root):
    res = _run(tiny_root("tiny_sl"), "tiny_sl")
    assert res["correct"], res["check"]
    assert set(res["check"]) == {"loss_gap", "moment_gap", "change_gap"}
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert set(res["metrics"]) == {"setup_s"}


def test_split_cnn_with_int8_kernel_matches_reference(tiny_root):
    res = _run(tiny_root("tiny_sl_int8", limits=INT8_LIMITS), "tiny_sl_int8")
    assert res["correct"], res["check"]


def test_split_lm_matches_reference_and_traces(tiny_root):
    res = _run(tiny_root("tiny_lm"), "tiny_lm", trace=True)
    assert res["correct"], res["check"]
    # no device plane on the CPU: the trace-read metrics stay silent and
    # the span-read ones report
    assert "host_ms.tokens" in res["metrics"]
    assert "idle_share.tokens" not in res["metrics"]
    assert list(res)[-1] == "check"

"""``bench/run.py`` reports device metrics only from a TPU: on the CPU it
exits non-zero and prints no result line; and in a directory that holds
only ``BENCHMARK.json`` and the benchmark's own files it does the same."""
import json
import os
import shutil
import subprocess
import sys

from benchtools import ROOT


def _run(cwd):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "smollm_sl_2k",
         "--seed", str(2 ** 31 + 3), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _no_result(proc):
    assert proc.returncode != 0
    for line in proc.stdout.splitlines():
        try:
            obj = json.loads(line)
        except ValueError:
            continue
        assert "metrics" not in obj


def test_refuses_cpu():
    proc = _run(ROOT)
    _no_result(proc)
    assert "no TPU" in proc.stderr


def test_benchmark_files_alone_do_not_run(tmp_path):
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    for p in bench["paths"]:
        shutil.copytree(os.path.join(ROOT, p), tmp_path / p,
                        ignore=shutil.ignore_patterns(".out", "__pycache__"))
    _no_result(_run(str(tmp_path)))

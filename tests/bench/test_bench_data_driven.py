"""A configuration, a traffic mix and a per-layer metric are added with new
files and new ``BENCHMARK.json`` entries only: the harness finds them by
name and reports the new metric in the new cell."""
import json
import os
import time

from bench import run
from benchtools import load


def test_new_cell_and_metric_from_files_only(tiny_root):
    root = tiny_root("tiny_lm")
    b = os.path.join(root, "bench")
    cfg = {**load(os.path.join(b, "configs", "tiny_lm-config.json")),
           "name": "throwaway-lm", "num_hidden_layers": 2}
    with open(os.path.join(b, "configs", "throwaway-lm.json"), "w") as f:
        json.dump(cfg, f)
    traffic = {**load(os.path.join(b, "traffic", "tiny_lm.json")),
               "clients": 1, "seq_len": 32}
    with open(os.path.join(b, "traffic", "throwaway_mix.json"), "w") as f:
        json.dump(traffic, f)
    with open(os.path.join(b, "limits", "throwaway.json"), "w") as f:
        json.dump({"limits": {"loss_gap": 1e-5, "change_gap": 2e-3}}, f)
    with open(os.path.join(b, "metrics", "round_p50_ms.py"), "w") as f:
        f.write("import numpy as np\n\n\ndef read(ctx):\n"
                "    return float(np.percentile(ctx.round_ms(), 50))\n")
    bench = load(os.path.join(root, "BENCHMARK.json"))
    bench["configs"].append({"name": "throwaway-lm", "source": "tiny",
                             "file": "bench/configs/throwaway-lm.json",
                             "reduced": [], "why": "tiny"})
    bench["workloads"].append({"name": "throwaway", "config": "throwaway-lm",
                               "traffic": "throwaway_mix", "chips": 1,
                               "why": "tiny"})
    bench["end_to_end"].append({"name": "round_p50_ms", "unit": "ms",
                                "better": "lower", "bound": 0.05,
                                "source": "host_clock",
                                "workloads": ["throwaway"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    res = run.run_cell("throwaway", 7, 0.3, False,
                       t_start=time.perf_counter(), allow_cpu=True, root=root)
    assert res["correct"], res["check"]
    assert set(res["metrics"]) == {"round_p50_ms", "setup_s"}
    assert res["metrics"]["round_p50_ms"]["value"] > 0


def test_reader_found_by_own_or_split_name(tmp_path):
    d = tmp_path / "metrics"
    d.mkdir()
    (d / "mfu.py").write_text("def read(ctx):\n    return 1.0\n")
    (d / "mfu.train.py").write_text("def read(ctx):\n    return 2.0\n")
    assert run.reader_path("mfu.tokens", str(tmp_path)) == str(d / "mfu.py")
    assert run.load_reader("mfu.train", str(tmp_path))(None) == 2.0
    assert run.load_reader("mfu.tokens", str(tmp_path))(None) == 1.0

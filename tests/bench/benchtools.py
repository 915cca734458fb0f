"""Helpers of the benchmark's CPU tests: the harness importable as
``bench``, and tiny cells in a throwaway root that holds the same kinds of
files as the repo's ``bench/`` (configurations, traffic, limits, metric
readers, peaks) for ``bench.run.run_cell(..., root=)``."""
import json
import os
import shutil
import sys

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

BENCH = os.path.join(ROOT, "bench")

# tiny cells: the repo's configurations and traffic cut to CPU size. The
# learning rates are small so that the program and the reference stay
# close over three rounds at batch 2 (at 1e-3 the tiny CNN's loss swings by
# tens of percent from round to round, and rounding grows with it)
TINY = {
    "tiny_lm": ("smollm-135m-split.json", "lm_sl_2k.json",
                dict(num_hidden_layers=4, hidden_size=64, intermediate_size=128,
                     num_attention_heads=4, num_key_value_heads=2, head_dim=16,
                     vocab_size=256),
                dict(seq_len=64, train_examples=16, lr=1e-4),
                "smollm_sl_2k"),
    "tiny_sl": ("mobilenetv2-224-split.json", "sl_224_int8.json",
                dict(image_size=32),
                dict(clients=2, batch=2, train_examples=48, test_examples=4,
                     lr=1e-5, link="none", link_kernel="xla"),
                None),
    "tiny_sl_int8": ("mobilenetv2-224-split.json", "sl_224_int8.json",
                     dict(image_size=32),
                     dict(clients=2, batch=2, train_examples=48,
                          test_examples=4, lr=1e-5),
                     None),
}
# limits of the tiny cells, from CPU readings of the program against the
# reference (seeds 1, 20-25 and 2**31 + 5): the tiny split LM and the
# tiny CNN without the link read under 1e-5 / 1e-4 / 1e-3; the control
# (the reference in bfloat16) reads above 4e-5 / 2e-3 / 4e-3 on the LM.
TINY_LIMITS = {"loss_gap": 1e-5, "moment_gap": 5e-4, "change_gap": 2e-3}
# With the int8 link on a 4x4x32 smashed tensor, one code that rounds the
# other way moves the loss by up to 1.5e-3 and a small leaf's moment by up
# to 0.2 (the same readings): the tiny int8 cell is held only to that.
INT8_LIMITS = {"loss_gap": 1e-2, "moment_gap": 0.5, "change_gap": 0.1}


def load(path):
    with open(path) as f:
        return json.load(f)


def make_root(path, cells, limits=TINY_LIMITS):
    """A root with BENCHMARK.json listing ``cells`` beside the repo's metric
    readers. A cell is a name of TINY, or a pair ``(name, description)``
    whose description is laid out as TINY's entries: the configuration and
    traffic files (names in the repo's ``bench/``, or paths), the overrides
    of each, and the cell whose metrics it reports. Each tiny cell reports
    what that cell reports (the CNN ones, cut from cells that are out of
    BENCHMARK.json until their comparison holds, report ``setup_s``)."""
    shutil.rmtree(path, ignore_errors=True)
    for d in ("configs", "traffic", "limits"):
        os.makedirs(os.path.join(path, "bench", d))
    shutil.copytree(os.path.join(BENCH, "metrics"),
                    os.path.join(path, "bench", "metrics"))
    shutil.copy(os.path.join(BENCH, "peaks.json"),
                os.path.join(path, "bench", "peaks.json"))
    bench = load(os.path.join(ROOT, "BENCHMARK.json"))
    bench["configs"], bench["workloads"] = [], []
    for cell in cells:
        name, desc = (cell, TINY[cell]) if isinstance(cell, str) else cell
        cfg_file, traffic_file, cfg_kw, traffic_kw, like = desc
        cfg = {**load(os.path.join(BENCH, "configs", cfg_file)), **cfg_kw,
               "name": name + "-config"}
        traffic = {**load(os.path.join(BENCH, "traffic", traffic_file)),
                   **traffic_kw}
        rel = f"bench/configs/{cfg['name']}.json"
        with open(os.path.join(path, rel), "w") as f:
            json.dump(cfg, f)
        with open(os.path.join(path, "bench", "traffic", name + ".json"),
                  "w") as f:
            json.dump(traffic, f)
        with open(os.path.join(path, "bench", "limits", name + ".json"),
                  "w") as f:
            json.dump({"limits": limits}, f)
        bench["configs"].append({"name": cfg["name"], "source": "tiny",
                                 "file": rel, "reduced": [], "why": "tiny"})
        bench["workloads"].append({"name": name, "config": cfg["name"],
                                   "traffic": name, "chips": 1,
                                   "why": "tiny"})
        for m in bench["end_to_end"] + bench["per_layer"]:
            if like in m.get("workloads", []):
                m["workloads"].append(name)
    with open(os.path.join(path, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f, indent=1)
    return path

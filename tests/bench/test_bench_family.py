"""What depends on the model is found by the configuration's ``family``,
one file per family and kind (``jobs/``, ``reference/``, ``flops/``) in the
cell's bench dir, with the harness's own as the fallback: a family is added
by new files alone, an unknown one fails naming itself, and the LM and CNN
families feed their cells what they fed them before the dispatch (pinned
from the harness that branched on the family in ``workload.py``)."""
import hashlib
import os
import time

import pytest

from bench import run, workload as wl
from benchtools import BENCH, TINY

ALIAS = "tiny_lm_alias"
# the family's own modules: each wraps the split LM's, the reference through
# a relative import, as a new family's modules would share ``common``
ALIAS_FILES = {
    "jobs": ("from bench.jobs import lm\n\n\n"
             "make_data = lm.make_data\n\n\n"
             "def make_spec(cell, seed):\n"
             "    return lm.make_spec(cell, seed)\n"),
    "reference": ("from . import lm\n\n\n"
                  "class Reference(lm.Reference):\n"
                  "    pass\n"),
    "flops": ("from bench.flops import lm\n\n\n"
              "def flops_per_round(config, traffic):\n"
              "    return lm.flops_per_round(config, traffic)\n"),
}


def _alias_cell(family):
    cfg_file, traffic_file, cfg_kw, traffic_kw, _ = TINY["tiny_lm"]
    return (ALIAS, (cfg_file, traffic_file, {**cfg_kw, "family": family},
                    traffic_kw, "smollm_sl_2k"))


def _write(root, kind, family, text):
    os.makedirs(os.path.join(root, "bench", kind), exist_ok=True)
    with open(os.path.join(root, "bench", kind, family + ".py"), "w") as f:
        f.write(text)


def _cell(root, name):
    return wl.load_cell(name, root=root, bench_dir=os.path.join(root, "bench"))


def test_new_family_from_files_only(tiny_root):
    root = tiny_root(_alias_cell(ALIAS))
    for kind, text in ALIAS_FILES.items():
        assert not os.path.exists(os.path.join(BENCH, kind, ALIAS + ".py"))
        _write(root, kind, ALIAS, text)
    cell = _cell(root, ALIAS)
    for kind in ALIAS_FILES:
        assert wl.family_module(cell, kind).__file__.startswith(root)
    res = run.run_cell(ALIAS, 11, 0.3, False, t_start=time.perf_counter(),
                       allow_cpu=True, root=root)
    assert res["correct"], res["check"]
    assert set(res["metrics"]) == {"tokens_per_s", "setup_s"}
    assert res["metrics"]["setup_s"]["value"] > 0


def test_unknown_family_fails_naming_it(tiny_root):
    root = tiny_root(_alias_cell("no_such_family"))
    with pytest.raises(ModuleNotFoundError) as err:
        run.run_cell(ALIAS, 11, 0.3, False, t_start=time.perf_counter(),
                     allow_cpu=True, root=root)
    msg = str(err.value)
    assert "'no_such_family'" in msg and "jobs/no_such_family.py" in msg
    assert os.path.join(root, "bench") in msg and BENCH in msg


def test_cell_dir_module_comes_before_the_harness_own(tiny_root):
    from bench.flops import lm as own
    root = tiny_root("tiny_lm")
    cell = _cell(root, "tiny_lm")
    assert wl.family_module(cell, "flops") is own
    _write(root, "flops", "lm",
           "def flops_per_round(config, traffic):\n    return 7.0\n")
    assert wl.model_flops_per_round(cell) == 7.0


# ---------------------------------------------------------------------------
# the LM and CNN cells' data and spec, pinned
# ---------------------------------------------------------------------------

PIN_SEED = 2 ** 31 + 5
DATA_SHA256 = {
    "tiny_lm": "5b5d723210ce82e3cdc9614e468d11f6264b245f01da50a0e3069fe3179f2372",
    "tiny_sl": "a28955edc80c3e0c107d907da1f2f50b13d231c533afa66ada25f97e48bd8b35",
}
_JOB = {"clients.num_clients": 2, "link_policy.compress": "none",
        "engine.kind": "sl", "engine.client_axis": "vmap",
        "engine.link_kernel": "xla", "global_rounds": 1, "local_steps": 2,
        "batch_size": 2, "seed": PIN_SEED, "cut_policy.mode": "fraction",
        "cut_policy.fraction": 0.25}
SPEC_FIELDS = {
    "tiny_lm": {**_JOB, "lr": 1e-4,
                "model.family": "transformer", "model.attn_impl": "pallas",
                "model.arch.name": "tiny_lm-config",
                "model.arch.family": "dense", "model.arch.n_layers": 4,
                "model.arch.d_model": 64, "model.arch.n_heads": 4,
                "model.arch.n_kv_heads": 2, "model.arch.head_dim": 16,
                "model.arch.d_ff": 128, "model.arch.vocab": 256,
                "model.arch.rope_theta": 10000.0,
                "model.arch.tie_embeddings": False,
                "model.arch.dtype": "bfloat16",
                "model.arch.source":
                    "https://huggingface.co/HuggingFaceTB/SmolLM-135M",
                "data.kind": "tokens", "data.seq_len": 64,
                "data.partition": "iid"},
    "tiny_sl": {**_JOB, "lr": 1e-5,
                "model.family": "cnn", "model.name": "mobilenetv2",
                "model.num_classes": 12, "model.arch": None,
                "model.attn_impl": "xla", "data.kind": "arrays",
                "data.image_size": 32, "data.partition": "classes",
                "data.classes_per_client": 3},
}


def _field(obj, dotted):
    for part in dotted.split("."):
        obj = getattr(obj, part)
    return obj


@pytest.mark.parametrize("name", sorted(DATA_SHA256))
def test_data_pinned(tiny_root, name):
    data = wl.make_data(_cell(tiny_root(name), name), PIN_SEED)
    h = hashlib.sha256()
    for a in data:
        h.update(repr((a.shape, str(a.dtype))).encode())
        h.update(a.tobytes())
    assert h.hexdigest() == DATA_SHA256[name]


@pytest.mark.parametrize("name", sorted(SPEC_FIELDS))
def test_spec_pinned(tiny_root, name):
    spec = wl.make_spec(_cell(tiny_root(name), name), PIN_SEED)
    got = {k: _field(spec, k) for k in SPEC_FIELDS[name]}
    assert got == SPEC_FIELDS[name]

"""A benchmark spec at a size the CPU runs in seconds, for the tests: a
two-layer dense GQA model (d_model 64, 4 query and 2 key/value heads of 16
dims, vocabulary 512, bf16) served through the same harness, with an
open-loop and a closed-loop mix."""
from __future__ import annotations

import glob
import json
import os
import shutil
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(BENCH)
for p in (BENCH, os.path.join(REPO, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

MODEL = {"num_hidden_layers": 2, "hidden_size": 64, "intermediate_size": 128,
         "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
         "vocab_size": 512, "mlp_activation": "gelu_tanh", "norm_type": "layer_norm",
         "norm_epsilon": 1e-06, "rope_theta": 10000.0,
         "tie_word_embeddings": False, "use_bias": True, "torch_dtype": "bfloat16"}
DIMS = {"n_layers": 2, "d_model": 64, "d_ff": 128, "n_heads": 4, "n_kv_heads": 2,
        "head_dim": 16, "vocab_size": 512}
CONFIG = {
    "name": "tiny", "source": "test", "reference_module": "dense_gqa",
    "model": MODEL,
    "repro": {"arch": "starcoder2-7b",
              "overrides": dict(DIMS, norm="layernorm", rope_theta=10000.0)},
    "deployment": {"engine": "slot", "chips": 1, "slots": 4, "max_len": 64},
    "tuning": {"donor": {"arch": "minitron-4b", "overrides": DIMS, "slots": 4,
                         "max_len": 64},
               "seed": 0},
}
TRAFFIC = {
    "chat": {"loop": "open", "rate_per_s": 4.0,
             "prompt_len": {"dist": "lognormal", "median": 12, "sigma": 0.5,
                            "min": 4, "max": 24},
             "output_len": {"dist": "lognormal", "median": 6, "sigma": 0.5,
                            "min": 3, "max": 10}},
    "decode": {"loop": "closed", "requests": 8,
               "prompt_len": {"dist": "uniform", "min": 4, "max": 8},
               "output_len": {"dist": "lognormal", "median": 8, "sigma": 0.3,
                              "min": 6, "max": 12}},
}
#: The tiny model's served tokens sit at most 0.015 logit below the f32
#: reference's best (seeds 11-13 on the CPU), its fp8 control's at least
#: 0.06 below, and a wrong token about a logit below.
LIMIT = 0.04
DEVICE = {"platform": "cpu", "kind": "TPU v5 lite", "count": 1}


#: Each cell of the real benchmark stands for the tiny cell with its loop.
AS_TINY = {"minitron-4b.chat": "tiny.chat", "starcoder2-7b.code": "tiny.chat",
           "minitron-4b.decode": "tiny.decode"}


def spec() -> dict:
    """The real ``BENCHMARK.json`` with its metrics, over the tiny cells."""
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        real = json.load(f)

    def retarget(m: dict) -> dict:
        if "workloads" not in m:
            return m
        return dict(m, workloads=sorted({AS_TINY[c] for c in m["workloads"]}))

    return dict(
        real, run_seconds=3,
        configs=[{"name": "tiny", "source": "test",
                  "file": "bench/configs/tiny.json", "reduced": [], "why": "test"}],
        workloads=[{"name": c, "config": "tiny", "traffic": c.split(".")[1],
                    "chips": 1, "why": "test"} for c in ("tiny.chat", "tiny.decode")],
        end_to_end=[retarget(m) for m in real["end_to_end"]],
        per_layer=[retarget(m) for m in real["per_layer"]])


def arch():
    """``bench/models/dense_gqa.py``, the tiny model's module, as the
    harness loads it from the repo."""
    import harness

    return harness.arch_module(CONFIG, "bench_tiny.CONFIG")


def write(root: str, *, limit: float | None = LIMIT) -> str:
    """Lay out the tiny spec under ``root`` as a checkout would hold it,
    with the repo's architecture modules."""
    for d in ("configs", "traffic", "limits", "models"):
        os.makedirs(os.path.join(root, "bench", d), exist_ok=True)
    for f in glob.glob(os.path.join(BENCH, "models", "*.py")):
        shutil.copy(f, os.path.join(root, "bench", "models"))

    def dump(path, obj):
        with open(os.path.join(root, path), "w") as f:
            json.dump(obj, f)

    dump("BENCHMARK.json", spec())
    dump("bench/configs/tiny.json", CONFIG)
    for name, t in TRAFFIC.items():
        dump(f"bench/traffic/{name}.json", t)
        if limit is not None:
            dump(f"bench/limits/tiny.{name}.json", {"max_logit_gap": {"limit": limit}})
    return root


@pytest.fixture
def tiny(tmp_path, monkeypatch):
    """The tiny spec as the checkout, with JAX's settings put back after."""
    import harness
    import jax

    write(str(tmp_path))
    monkeypatch.setattr(harness, "ROOT", str(tmp_path))
    monkeypatch.setattr(harness, "CACHE", str(tmp_path / "cache"))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "unused"))
    saved = {k: getattr(jax.config, k) for k in (
        "jax_compilation_cache_dir", "jax_persistent_cache_min_compile_time_secs",
        "jax_enable_compilation_cache")}
    jax.config.update("jax_enable_compilation_cache", False)
    yield tmp_path
    for k, v in saved.items():
        jax.config.update(k, v)

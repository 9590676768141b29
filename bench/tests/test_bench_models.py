"""The architecture modules (``bench/models/<name>.py``) and how a
configuration file names its module.

The dense module must reproduce, bit for bit, what the benchmark computed
before its equations moved out of the shared files: the golden digests and
counts below were taken from the shared ``weights.py``, ``reference.py`` and
``counts.py`` that held the dense GQA equations, on the CPU."""
import dataclasses
import functools
import hashlib
import json
import os

import jax
import numpy as np
import pytest

import bench_tiny
import harness
import reference
import run
import weights
from bench_tiny import tiny  # noqa: F401  (a fixture)

ARCH = bench_tiny.arch()
PROMPT = [5, 17, 301, 42, 9, 77, 140, 3, 256, 11, 64]
SERVED = [7, 300, 12, 450, 33, 2, 99, 8]
SEEDS = [2**31 + 7, 12345]
MODELS = {"layer_norm": bench_tiny.MODEL,
          "rms_norm": dict(bench_tiny.MODEL, norm_type="rms_norm", use_bias=False)}

GOLDEN_WEIGHTS = {
    ("layer_norm", SEEDS[0]): "646df71b8c41661ba19db3bac4c9501f",
    ("layer_norm", SEEDS[1]): "1e61464b64c2368c9c9f1c0b991830c6",
    ("rms_norm", SEEDS[0]): "9be66e6909a35d4f4d24c2dad091b956",
    ("rms_norm", SEEDS[1]): "3729ab2fbb85e239203287d6d2ab597f",
}
GOLDEN_HIDDEN = {
    ("layer_norm", None): "c017ef7c1113d9ff5c469c6f81d720c3",
    ("layer_norm", "fp8"): "6561886007d6f613bde224e4a75c04d9",
    ("rms_norm", None): "ee77668e4f8dcc0c359568ef0f24789f",
    ("rms_norm", "fp8"): "47db2ee8fbdededeb900fc880df86b5f",
}
GOLDEN_GAPS = {"layer_norm": "6a05c68da4628a7ac863d9db5f1166c3",
               "rms_norm": "7b46e1fecec5cc98e32f37cb25a1181a"}
ATTENDED = [1, 129, 1020, 2048]
PREFILL_LENGTHS = (1, 64, 1020, 2048)
GOLDEN_COUNTS = {
    "minitron-4b": {
        "prefill_flops": [6807748608, 337398202368, 5545505587200, 11546847608832],
        "decode_flops": 28486926336, "decode_bytes": 7227871232,
        "class_weights": {"matmul": 1711276032, "matmul_bias_gelu": 905969664,
                          "matmul_lmhead": 786432000}},
    "starcoder2-7b": {
        "prefill_flops": [7399047168, 445595516928, 7238699384832, 14844161949696],
        "decode_flops": 30538137600, "decode_bytes": 7505057792,
        "class_weights": {"matmul": 754974720, "matmul_bias_gelu": 1359249408,
                          "matmul_lmhead": 226492416, "matmul_bias": 1359028224}},
}


def _digest(arrays) -> str:
    h = hashlib.sha256()
    for a in arrays:
        a = np.asarray(a)
        h.update(f"{a.dtype}{a.shape}".encode())
        h.update(a.tobytes())
    return h.hexdigest()[:32]


@pytest.mark.parametrize("norm,seed", sorted(GOLDEN_WEIGHTS))
def test_dense_weights_are_bit_identical(norm, seed):
    w = weights.make(ARCH, MODELS[norm], seed)
    assert _digest(jax.tree_util.tree_leaves(w)) == GOLDEN_WEIGHTS[norm, seed]


@pytest.mark.parametrize("norm,quant", sorted(GOLDEN_HIDDEN, key=str))
def test_dense_reference_hidden_is_bit_identical(norm, quant):
    m = MODELS[norm]
    tokens = np.zeros(128, np.int32)
    seq = PROMPT + SERVED[:-1]
    tokens[:len(seq)] = seq
    h = ARCH.final_hidden(weights.make(ARCH, m, SEEDS[0]), ARCH.ref_config(m),
                          tokens, quant)
    assert _digest([h]) == GOLDEN_HIDDEN[norm, quant]


@pytest.mark.parametrize("norm", sorted(GOLDEN_GAPS))
def test_dense_gaps_are_bit_identical(norm):
    m = MODELS[norm]
    w = weights.make(ARCH, m, SEEDS[0])
    hidden = functools.partial(ARCH.final_hidden, w, ARCH.ref_config(m))
    g = reference.gaps(w, hidden, PROMPT, SERVED, control=True)
    assert _digest([g["served"], g["control"]]) == GOLDEN_GAPS[norm]


@pytest.mark.parametrize("config", sorted(GOLDEN_COUNTS))
def test_dense_counts_of_the_full_configurations_are_unchanged(config):
    with open(os.path.join(bench_tiny.BENCH, "configs", f"{config}.json")) as f:
        conf = json.load(f)
    assert conf["reference_module"] == "dense_gqa"
    d = ARCH.dims(conf["model"])
    class_weights = run.reader("roofline.decode_matmul").__globals__["class_weights"]
    got = {"prefill_flops": [d.prefill_flops(n) for n in PREFILL_LENGTHS],
           "decode_flops": d.decode_flops(ATTENDED),
           "decode_bytes": d.decode_bytes(ATTENDED),
           "class_weights": class_weights(d)}
    assert got == GOLDEN_COUNTS[config]


# ---------------------------------------------------------------------------
# How a configuration file names its module
# ---------------------------------------------------------------------------


def _rewrite_config(root, **change) -> None:
    path = root / "bench" / "configs" / "tiny.json"
    conf = {k: v for k, v in json.loads(path.read_text()).items()
            if k != "reference_module"}
    path.write_text(json.dumps(dict(conf, **change)))


def test_a_cell_carries_the_module_its_file_names(tiny):
    cell, _ = harness.load_cell("tiny.chat")
    assert cell.arch.__file__ == str(tiny / "bench" / "models" / "dense_gqa.py")
    # the checkout's copy, not the repo's module
    assert cell.arch is not ARCH
    assert dataclasses.asdict(cell.arch.dims(cell.model)) == \
        dataclasses.asdict(ARCH.dims(bench_tiny.MODEL))


@pytest.mark.parametrize("change,match", [
    ({}, "no 'reference_module'"),
    ({"reference_module": "moe_gqa"}, "unknown reference_module 'moe_gqa'"),
    ({"reference_module": "../tests/bench_tiny"}, "unknown reference_module"),
    ({"reference_module": 3}, "unknown reference_module 3"),
], ids=["missing", "unknown", "path", "not_a_name"])
def test_a_missing_or_unknown_module_is_refused_with_the_file_name(
        tiny, change, match):
    _rewrite_config(tiny, **change)
    with pytest.raises(ValueError, match=match) as e:
        harness.load_cell("tiny.chat")
    assert str(e.value).startswith("bench/configs/tiny.json: ")

"""The plain float32 reference against the program at a reduced size on the
CPU: ``model.prefill`` and then decoding through the slot engine's cache
(``ref`` backend) give the reference's logits, and the fp8 control does
not."""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bench_tiny
import reference
import weights
from repro.models.build import build_model
from repro.serving import ServingEngine

SEED = 2**31 + 7
PROMPT = [5, 17, 301, 42, 9, 77, 140, 3, 256, 11, 64]
NEW = 8
ARCH = bench_tiny.arch()


def _conf(norm_type: str, dtype: str) -> dict:
    model = dict(bench_tiny.MODEL, norm_type=norm_type, torch_dtype=dtype)
    repro = dict(bench_tiny.CONFIG["repro"])
    repro["overrides"] = dict(
        repro["overrides"], dtype=dtype,
        norm={"layer_norm": "layernorm", "rms_norm": "rmsnorm"}[norm_type])
    return dict(bench_tiny.CONFIG, model=model, repro=repro)


def _served(conf: dict):
    """Program logits: prefill's last position, then each decode step."""
    cfg = ARCH.program_config(conf)
    model = build_model(cfg)
    params = weights.make(ARCH, conf["model"], SEED)
    logits, _ = model.prefill(params, {"tokens": jnp.asarray([PROMPT], jnp.int32)},
                              max_len=32)
    engine = ServingEngine(model, params, slots=2, max_len=32)
    engine.add_request([1, 2, 3], max_new_tokens=NEW + 4)   # a second live slot
    req = engine.add_request(PROMPT, max_new_tokens=NEW)
    slot = next(s for s, r in engine.active.items() if r is req)
    steps = [np.asarray(logits[0], np.float32)]
    while not req.done:
        engine.step()
        steps.append(np.asarray(engine.last_logits[slot], np.float32))
    return params, req.generated, np.stack(steps[:len(req.generated)])


def _hidden(params, conf: dict):
    return functools.partial(ARCH.final_hidden, params, ARCH.ref_config(conf["model"]))


def _reference_logits(params, m: dict, seq: list[int]) -> np.ndarray:
    h = ARCH.final_hidden(params, ARCH.ref_config(m), np.asarray(seq, np.int32))
    w = params["lm_head"].astype(jnp.float32)
    return np.asarray(jnp.dot(h, w, precision=reference.HIGHEST))


@pytest.mark.parametrize("norm_type", ["layer_norm", "rms_norm"])
def test_reference_matches_prefill_and_slot_decode_in_f32(norm_type):
    conf = _conf(norm_type, "float32")
    params, served, program = _served(conf)
    ref = _reference_logits(params, conf["model"], PROMPT + served[:-1])
    ref = ref[len(PROMPT) - 1:len(PROMPT) - 1 + len(served)]
    assert program.shape == ref.shape == (NEW, 512)
    np.testing.assert_allclose(program, ref, atol=2e-4, rtol=0)
    assert float(np.abs(ref).std()) > 0.3          # logits with a real spread
    assert list(ref.argmax(-1)) == served


def test_gaps_of_served_tokens_and_the_fp8_control():
    conf = _conf("layer_norm", "bfloat16")
    params, served, _ = _served(conf)
    g = reference.gaps(params, _hidden(params, conf), PROMPT, served, control=True)
    assert g["served"].shape == g["control"].shape == (NEW,)
    assert np.all(g["served"] >= 0) and np.all(g["control"] >= 0)
    assert g["served"].max() < 0.05        # bf16 rounding around the f32 best
    # fp8 puts other tokens first, well below the reference's best
    assert g["control"].max() > 3 * max(g["served"].max(), 0.01)


def test_gaps_read_the_served_token_not_the_best():
    conf = _conf("layer_norm", "float32")
    params, served, _ = _served(conf)
    wrong = list(served)
    wrong[3] = (wrong[3] + 1) % 512
    ref = _reference_logits(params, conf["model"], PROMPT + served[:-1])
    row = ref[len(PROMPT) - 1 + 3]
    g = reference.gaps(params, _hidden(params, conf), PROMPT, wrong[:4])
    assert g["served"][3] == pytest.approx(row.max() - row[wrong[3]], abs=1e-4)
    assert g["served"][:3].max() < 1e-4


@pytest.mark.parametrize("change,match", [
    ({"mlp_activation": "relu2"}, "tanh-GELU"),
    ({"rotary_fraction": 0.5}, "whole heads"),
    ({"tie_word_embeddings": True}, "untied head"),
], ids=["activation", "rotary_fraction", "tied_head"])
def test_reference_refuses_what_it_does_not_compute(change, match):
    with pytest.raises(ValueError, match=match):
        ARCH.ref_config(dict(bench_tiny.MODEL, **change))


def test_weights_are_seeded_and_in_the_served_dtype():
    m = bench_tiny.MODEL
    a, b = weights.make(ARCH, m, SEED), weights.make(ARCH, m, SEED)
    c = weights.make(ARCH, m, SEED + 2**32)    # differs only above 32 bits
    la, lb, lc = (jax.tree_util.tree_leaves(x) for x in (a, b, c))
    assert all(x.dtype == jnp.bfloat16 for x in la)
    assert all(np.array_equal(np.asarray(x), np.asarray(y)) for x, y in zip(la, lb))
    assert not np.array_equal(np.asarray(a["embed"]), np.asarray(c["embed"]))
    shapes = jax.tree_util.tree_map(lambda x: x.shape, a)
    program = jax.eval_shape(build_model(ARCH.program_config(bench_tiny.CONFIG)).init,
                             jax.random.PRNGKey(0))
    assert shapes == jax.tree_util.tree_map(lambda x: x.shape, program)


def test_ref_cfg_is_hashable_for_jit():
    cfg = ARCH.ref_config(bench_tiny.MODEL)
    assert hash(cfg) == hash(dataclasses.replace(cfg))

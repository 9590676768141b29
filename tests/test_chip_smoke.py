"""chip_smoke.py rehearsed on the CPU: its phases at reduced size (Pallas in
interpret mode), its refusal to run without a TPU, and the compile cache it
and the other entry points share."""
import importlib.util
import json
import os
import subprocess
import sys
import textwrap

import jax
import pytest

from repro.launch import compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def smoke(tmp_path, monkeypatch):
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(REPO, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    monkeypatch.setattr(mod, "OUT", str(tmp_path))
    return mod


def _phases(out: str) -> dict:
    rows = [json.loads(line) for line in out.splitlines() if line.startswith("{")]
    return {r["phase"]: r for r in rows if "phase" in r}


def test_one_chip_path_at_reduced_size(smoke, capsys):
    smoke.one_chip("smoke", "tpu-v5e", require_compiled=False)
    phases = _phases(capsys.readouterr().out)
    plan, served, logits = phases["plan"], phases["serve"], phases["logits"]
    assert plan["donor_records"] > 0 and plan["jobs_drained"] > 0
    tiers = plan["tiers"]
    assert tiers["exact"] + tiers["transfer"] > plan["plan_entries"] / 2
    # the drained jobs publish donor schedules under the served workloads
    assert plan["exact_from_donor"] == tiers["exact"]
    assert served["requests"] == smoke.REQUESTS
    assert served["tuned_share"] > 0.5
    assert served["pallas_interpret"] is True      # CPU: interpret mode
    assert logits["greedy_agree"] == smoke.PROMPTS
    assert logits["rel_err"] <= smoke.LOGIT_RTOL


def test_refuses_to_run_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, os.path.join(REPO, "chip_smoke.py")],
                         capture_output=True, text=True, env=env, timeout=300)
    assert out.returncode != 0
    assert "no TPU found" in out.stderr
    assert '"ok"' not in out.stdout


def test_four_chip_path_on_virtual_devices():
    code = textwrap.dedent(f"""
        import importlib.util, json
        spec = importlib.util.spec_from_file_location(
            "chip_smoke", {os.path.join(REPO, "chip_smoke.py")!r})
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        mod.four_chips("smoke")
    """)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.path.join(REPO, "src"))
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=600)
    assert out.returncode == 0, out.stderr
    train = _phases(out.stdout)["train"]
    assert train["full_devices"] == 4 and len(train["full_losses"]) == 3
    assert train["rel_diff"] <= train["rtol"]


def test_compile_cache_location(monkeypatch):
    prev = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
        jax.config.update("jax_compilation_cache_dir", None)
        assert compile_cache.enable_compile_cache() == "/somewhere/else"
        assert jax.config.jax_compilation_cache_dir is None   # left to JAX
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        path = compile_cache.enable_compile_cache()
        assert path == os.path.join(REPO, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)

"""chip_smoke.py off the chip: the CLI refuses to run (no CPU
fallback), its phase functions pass their own comparisons at a tiny
config on the CPU mesh, a wrong kernel is caught, and the compile-cache
helper places the cache where the entry scripts are promised."""

import dataclasses
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import chip_smoke
from apex_tpu.models.gpt import GPTConfig
from apex_tpu.utils import compile_cache

TINY = GPTConfig(vocab_size=512, seq_len=64, hidden=64, num_layers=2,
                 num_heads=4, dropout=0.0, use_flash_attention=True)


@pytest.mark.parametrize("argv", [[], ["--multichip"]],
                         ids=["one_chip", "multichip"])
def test_cli_fails_without_a_tpu(argv):
    r = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chip_smoke.py"), *argv],
        capture_output=True, text=True, timeout=120,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert r.returncode != 0
    lines = r.stdout.strip().splitlines()
    assert len(lines) == 1, "nothing may be built before the refusal"
    last = json.loads(lines[-1])
    assert last["ok"] is False
    assert last["device"]["platform"] == "cpu"


@pytest.mark.parametrize("module", ["apex_tpu.parallel.multiproc", "bench",
                                    "chip_smoke"])
def test_import_initialises_no_backend(module):
    """One process per chip: the multiproc launcher's parent (which only
    imports) and anything importing the entry scripts must stay off
    jax.devices(), or they would hold the chip their children need."""
    code = (f"import {module}\n"
            "from jax._src import xla_bridge\n"
            "assert not xla_bridge.backends_are_initialized()\n")
    r = subprocess.run([sys.executable, "-c", code], cwd=ROOT,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr[-2000:]


def test_train_phase_tiny():
    rec = chip_smoke.phase_train(TINY, jax.devices(), batch=4, steps=6,
                                 seed=0, require_chip=False)
    assert len(rec["losses"]) == 6 and rec["losses"][-1] < rec["losses"][0]
    assert rec["sentry"]["steady_recompiles"] == 0
    assert rec["sentry"]["n_compiles"] == 1   # lower().compile() was reused


def test_train_phase_requires_the_kernels_on_chip():
    """On the CPU the step holds no Mosaic kernel — exactly what the
    smoke must refuse when it happens on the chip."""
    with pytest.raises(RuntimeError, match="tpu_custom_call"):
        chip_smoke.phase_train(TINY, jax.devices(), batch=4, steps=3,
                               seed=0, require_chip=True)


def test_multichip_phase_tiny():
    runs = chip_smoke.phase_multichip(TINY, jax.devices()[:4], batch=4,
                                      steps=3, seed=0, require_chip=False)
    assert set(runs) == {"tp1", "monolithic", "chunked"}
    assert runs["chunked"]["collectives"]["collective-permute"] > 0
    assert runs["monolithic"]["collectives"]["collective-permute"] == 0
    assert runs["monolithic"]["dp"] == runs["monolithic"]["tp"] == 2


def test_multichip_phase_catches_a_diverging_run(monkeypatch):
    real = chip_smoke.train_run

    def skewed(cfg, devices, tp, *a, **kw):
        rec = real(cfg, devices, tp, *a, **kw)
        if tp == 2:
            rec["losses"][-1] *= 1.05
        return rec

    monkeypatch.setattr(chip_smoke, "train_run", skewed)
    with pytest.raises(RuntimeError, match="differ by more than rtol"):
        chip_smoke.phase_multichip(TINY, jax.devices()[:4], batch=4,
                                   steps=3, seed=0, require_chip=False)


def test_serve_phase_tiny():
    from apex_tpu.serve import build_flagship_engine

    eng = build_flagship_engine(False, seed=0)
    rec = chip_smoke.phase_serve(eng, n_requests=8, min_prompt=2,
                                 max_new=8, seed=0)
    assert rec["top1_matches"] == 8 and len(rec["first_tokens"]) == 8


def test_serve_phase_catches_other_weights():
    """The first tokens are held to a forward of the SAME weights: an
    engine answering from different ones must fail the phase."""
    from apex_tpu.serve import build_flagship_engine

    eng = build_flagship_engine(False, seed=0)
    real_run = eng.run

    def run_then_swap():
        out = real_run()
        eng.params = build_flagship_engine(False, seed=1).params
        return out

    eng.run = run_then_swap
    with pytest.raises(RuntimeError, match="disagree with the full forward"):
        chip_smoke.phase_serve(eng, n_requests=8, min_prompt=2, max_new=8,
                               seed=0)


def _tiny_adam_case():
    from apex_tpu.ops import optimizer_kernels as K
    return chip_smoke.adam_case("adam_tiny", K.FLAT_TILE, jnp.float32)


def test_kernel_case_passes_and_reports():
    rec = chip_smoke.run_kernel_case(_tiny_adam_case(), seed=0,
                                     require_chip=False)
    assert rec["kernel"] == "adam_tiny" and len(rec["max_abs_err"]) == 3


def test_kernel_case_catches_a_wrong_kernel():
    case = _tiny_adam_case()
    wrong = dataclasses.replace(
        case, kernel=lambda p, m, v, g: case.reference(p * 1.001, m, v, g))
    with pytest.raises(RuntimeError, match="disagree"):
        chip_smoke.run_kernel_case(wrong, seed=0, require_chip=False)


def test_kernel_case_refuses_a_silent_reference():
    with pytest.raises(RuntimeError, match="jnp reference instead"):
        chip_smoke.run_kernel_case(_tiny_adam_case(), seed=0,
                                   require_chip=True)


# --------------------------- the compile cache ---------------------------

@pytest.fixture
def config_updates(monkeypatch):
    """jax.config.update calls the helper makes, recorded and not
    applied: the test process keeps its cache off."""
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: calls.append((name, value)))
    return calls


def test_cache_dir_from_env_is_left_to_jax(monkeypatch, config_updates):
    monkeypatch.setenv(compile_cache.ENV_CACHE_DIR, "/somewhere/else")
    assert compile_cache.enable_compile_cache() == "/somewhere/else"
    assert config_updates == []


def test_cache_dir_defaults_to_the_checkout(monkeypatch, config_updates):
    monkeypatch.delenv(compile_cache.ENV_CACHE_DIR, raising=False)
    want = os.path.join(ROOT, ".jax_cache")
    assert compile_cache.enable_compile_cache() == want
    assert compile_cache.enable_compile_cache() == want
    assert config_updates == [("jax_compilation_cache_dir", want)] * 2


def test_cache_dir_is_the_same_in_every_process(tmp_path):
    env = {k: v for k, v in os.environ.items()
           if k != compile_cache.ENV_CACHE_DIR}
    env["PYTHONPATH"] = ROOT
    code = ("import jax\n"
            "from apex_tpu.utils.compile_cache import enable_compile_cache\n"
            "print(enable_compile_cache())\n"
            "print(jax.config.jax_compilation_cache_dir)\n")
    seen = set()
    for cwd in (ROOT, str(tmp_path)):
        r = subprocess.run([sys.executable, "-c", code], cwd=cwd, env=env,
                           capture_output=True, text=True, timeout=120)
        assert r.returncode == 0, r.stderr[-2000:]
        seen.update(r.stdout.split())
    assert seen == {os.path.join(ROOT, ".jax_cache")}

"""chip_smoke.py rehearsed on the CPU, plus the device-setup helpers it uses.

The smoke's phases run here at smoke size with the serving kernel in
interpret mode (the same code paths the chip run takes at published widths),
and the script itself must refuse to run — exit non-zero, print no result
line — without a TPU or outside the repository.
"""
import importlib.util
import os
import pathlib
import shutil
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro.configs import get_config, get_smoke_config
from repro.launch import device, roofline
from repro.models import get_model
from repro.models.params import count_params

ROOT = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def tiny(smoke):
    """The smoke config's shape (bf16 weights, kernel on) at smoke widths:
    block 16 and a 2-page decode budget, so 40-token prompts over a
    4-page ring really select."""
    cfg = get_smoke_config("qwen3-1.7b", param_dtype="bfloat16",
                           attn_use_kernel=True, attn_interpret=True)
    return cfg, smoke.make_params(cfg, seed=0)


def test_parity_phase_kernel_matches_jnp(smoke, tiny):
    cfg, params = tiny
    err = smoke.parity_phase(cfg, params, slots=2, max_len=64, chunk=8,
                             n_chunks=5, n_decode=2)
    assert 0.0 <= err <= smoke.PARITY_BOUND


def test_serve_phase_counts_every_token(smoke, tiny):
    cfg, params = tiny
    res = smoke.serve_phase(cfg, params, slots=2, max_len=64, chunk=8,
                            prompt_lens=(5, 30, 12), new_tokens=3)
    assert res["requests"] == 3
    assert res["prompt_tokens"] == 47 and res["generated_tokens"] == 9
    assert res["prefill_dispatches"] >= 4  # the 30-token prompt alone takes 4


def test_parity_inputs_are_ragged_and_in_vocab(smoke):
    cfg = get_smoke_config("qwen3-1.7b")
    tokens, num_valid, decode = smoke.parity_inputs(cfg, 4, 16, 3, 2, seed=1)
    assert tokens.shape == (3, 4, 16) and decode.shape == (2, 4)
    assert (num_valid[:-1] == 16).all()
    assert len(set(num_valid[-1].tolist())) == 4
    assert tokens.min() >= 1 and tokens.max() < cfg.vocab


def test_compare_reports_relative_error_and_agreement(smoke):
    b = np.array([[[1.0, 0.0, 0.0], [0.0, 2.0, 0.0]]])
    a = b.copy()
    a[0, 1, 0] = 0.2
    err, agree = smoke.compare(a, b)
    assert err == pytest.approx(0.1)
    assert agree == 1.0


@pytest.mark.parametrize("where", ["repo", "alone"])
def test_refuses_without_tpu(tmp_path, where):
    script = ROOT / "chip_smoke.py"
    if where == "alone":  # a directory holding chip_smoke.py and nothing else
        script = pathlib.Path(shutil.copy(script, tmp_path))
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    r = subprocess.run([sys.executable, str(script)], capture_output=True,
                       text=True, env=env, cwd=script.parent, timeout=300)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout


def test_qwen3_1_7b_matches_published_config():
    cfg = get_config("qwen3-1.7b")
    assert (cfg.num_layers, cfg.d_model, cfg.num_heads, cfg.kv_heads,
            cfg.hd, cfg.d_ff, cfg.vocab) == (28, 2048, 16, 8, 128, 6144,
                                             151936)
    assert cfg.tie_embeddings
    n = count_params(get_model(cfg).param_specs(cfg))
    # 1.72B with tied embeddings (the table is padded to 152064 rows);
    # an untied head would add another 0.31B
    assert 1.70e9 < n < 1.75e9


def test_kernel_interpret_is_explicit():
    if jax.devices()[0].platform == "tpu":
        assert device.kernel_interpret(False) is False
    else:
        with pytest.raises(SystemExit, match="--interpret"):
            device.kernel_interpret(False)
    assert device.kernel_interpret(True) is True


def test_device_summary_names_platform_kind_count():
    d = device.device_summary()
    assert set(d) == {"platform", "kind", "count"}
    assert d["platform"] == jax.devices()[0].platform
    assert d["count"] == len(jax.devices())


def test_compile_cache_dir(monkeypatch):
    was = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
        assert device.enable_compile_cache() == "/elsewhere/cache"
        assert jax.config.jax_compilation_cache_dir == was  # JAX's own
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        path = device.enable_compile_cache()
        assert path == str(ROOT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", was)


def test_roofline_peaks_keyed_by_device_kind():
    assert roofline.peaks("TPU v5 lite")["flops"] == 197e12
    with pytest.raises(ValueError, match="no peak table"):
        roofline.peaks("cpu")
    with pytest.raises(ValueError):
        roofline.analyze({"status": "ok", "arch": "a", "shape": "s",
                          "mesh": "m"})

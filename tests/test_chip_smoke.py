"""chip_smoke.py's phases on tiny configs (CPU, kernels interpreted), its
refusal to run without a TPU, and the compile-cache placement."""
import os
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from repro.configs import reduced_config
from repro.launch import compile_cache

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))
import chip_smoke  # noqa: E402


def test_phase_train_reduced(capsys):
    res = chip_smoke.phase_train(
        "mamba2-370m", reduced=True, batch=2, seq=32, steps=3
    )
    assert len(res["step_times"]) == 3
    assert "[train] mamba2-370m" in capsys.readouterr().out


def test_phase_serve_and_curve_reduced(capsys):
    cfg = reduced_config("mamba2-370m")
    engine = chip_smoke.phase_serve(
        cfg, n_requests=5, batch_size=2, max_len=64, prompt_lens=(8, 16),
        new_tokens=4,
    )
    chip_smoke.phase_serve_curve(engine, "cpu", batch_sizes=(1, 2), steps=2)
    out = capsys.readouterr().out
    assert "[serve] " in out and "budgets_met=True" in out
    assert "[serve_curve] device_kind='cpu'" in out


def test_phase_prefill_reduced(capsys):
    chip_smoke.phase_prefill(reduced_config("h2o-danube-3-4b"), seq=64)
    assert "finite=True" in capsys.readouterr().out


def test_phase_kernels_small(capsys):
    chip_smoke.phase_kernels(
        flash=(1, 256, 4, 2, 128), ssd=(1, 256, 2, 64, 128),
        norm_dims=(128, 384), norm_rows=64,
    )
    out = capsys.readouterr().out
    assert out.count("within_tol=True") == 5


def test_main_refuses_a_cpu(capsys):
    assert jax.devices()[0].platform == "cpu"
    with pytest.raises(SystemExit) as e:
        chip_smoke.main()
    assert e.value.code not in (0, None)
    assert '"ok"' not in capsys.readouterr().out


def test_script_alone_fails(tmp_path):
    """Without the rest of the repo the script exits non-zero, silently."""
    (tmp_path / "chip_smoke.py").write_text(
        (ROOT / "chip_smoke.py").read_text()
    )
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    proc = subprocess.run(
        [sys.executable, "chip_smoke.py"], cwd=tmp_path, env=env,
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout


@pytest.fixture
def cache_dir_restored():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_compile_cache_follows_env(monkeypatch, tmp_path, cache_dir_restored):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == str(tmp_path)


def test_compile_cache_default_is_fixed(monkeypatch, cache_dir_restored):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    first = compile_cache.enable_compile_cache()
    assert first == compile_cache.enable_compile_cache()
    assert first == str(ROOT / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == first

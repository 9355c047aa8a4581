"""Rules that keep the suite honest about the device it runs on: the chip
smoke script refuses a host without a TPU, JAX's compilation cache is
placed only by entry points and only where the rule says, client
processes are refused where they would need the device, and roofline
peaks come from the device kind."""

import json
import os
import shutil
import subprocess
import sys
import types

import pytest

import jax

from repro.core import engine as engine_mod
from repro.core.metrics import TPUv5e, device_peaks

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_CACHE_KEYS = (
    "jax_compilation_cache_dir",
    "jax_persistent_cache_min_compile_time_secs",
    "jax_persistent_cache_min_entry_size_bytes",
)


def _last_line(text: str) -> str:
    lines = [line for line in text.splitlines() if line.strip()]
    return lines[-1] if lines else ""


@pytest.mark.parametrize("lonely", [False, True], ids=["checkout", "alone"])
def test_chip_smoke_fails_without_a_tpu(tmp_path, lonely):
    script = os.path.join(ROOT, "chip_smoke.py")
    if lonely:  # a directory holding the script and nothing else
        script = shutil.copy(script, tmp_path / "chip_smoke.py")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, str(script)], cwd=tmp_path, env=env,
        capture_output=True, text=True, timeout=300,
    )
    assert out.returncode != 0, out.stdout
    last = _last_line(out.stdout)
    assert '"ok": true' not in last
    if last.startswith("{"):
        assert json.loads(last).get("ok") is not True


@pytest.fixture
def cache_config():
    """Restore JAX's cache settings after a test that places the cache."""
    saved = {k: getattr(jax.config, k) for k in _CACHE_KEYS}
    yield
    for k, v in saved.items():
        jax.config.update(k, v)


def test_cache_env_var_wins_and_no_directory_is_set(
    cache_config, monkeypatch, tmp_path
):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "env"))
    before = jax.config.jax_compilation_cache_dir
    path = engine_mod.enable_compile_cache(str(tmp_path / "flag"))
    assert path == str(tmp_path / "env")
    assert jax.config.jax_compilation_cache_dir == before


def test_cache_dir_flag_then_fixed_checkout_path(cache_config, monkeypatch, tmp_path):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = engine_mod.enable_compile_cache(str(tmp_path))
    assert path == os.path.join(str(tmp_path), "jax-persistent")
    assert jax.config.jax_compilation_cache_dir == path
    # The fallback is the checkout's own fixed directory, the same in every
    # process: never a temporary, pid- or time-derived path.
    fixed = engine_mod.enable_compile_cache()
    assert fixed == os.path.join(ROOT, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == fixed


def test_nothing_places_the_cache_on_import(tmp_path):
    env = {k: v for k, v in os.environ.items() if k != "JAX_COMPILATION_CACHE_DIR"}
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    code = (
        "import repro.core, repro.core.suite, repro.core.engine\n"
        "from repro.core.engine import Engine\n"
        f"Engine(cache_dir={str(tmp_path)!r})\n"
        "import jax\n"
        "print(jax.config.jax_compilation_cache_dir)\n"
    )
    out = subprocess.run(
        [sys.executable, "-c", code], cwd=ROOT, env=env,
        capture_output=True, text=True, timeout=300,
    )
    assert out.returncode == 0, out.stderr
    assert _last_line(out.stdout) == "None"


def test_suite_main_places_the_cache_from_cache_dir(monkeypatch, tmp_path):
    from repro.core import suite

    calls = []
    monkeypatch.setattr(suite, "enable_compile_cache", calls.append)
    rc = suite.main([
        "--names", "pathfinder", "--cache-dir", str(tmp_path),
        "--iters", "1", "--warmup", "0", "--no-backward",
    ])
    assert rc == 0 and calls == [str(tmp_path)]


@pytest.mark.parametrize(
    "platform,kind,want",
    [("tpu", "TPU v5 lite", TPUv5e), ("cpu", "cpu", TPUv5e), ("tpu", "TPU v4", None)],
)
def test_roofline_peaks_keyed_by_device_kind(platform, kind, want):
    device = types.SimpleNamespace(platform=platform, device_kind=kind)
    if want is None:
        with pytest.raises(ValueError, match="no roofline peaks"):
            device_peaks(device)
    else:
        assert device_peaks(device) is want

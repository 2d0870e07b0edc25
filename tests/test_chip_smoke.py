"""``chip_smoke.py`` off the chip: it refuses to run without a TPU, and its
phases and checks pass at a tiny size with the kernels in interpret mode."""

import importlib.util
import json
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_refuses_without_tpu(chip_smoke, capsys):
    assert chip_smoke.main(["--rows", "1000"]) != 0
    out = capsys.readouterr()
    assert "needs a TPU" in out.err
    assert "{" not in out.out


def test_tiny_run_passes_its_checks(chip_smoke, monkeypatch):
    monkeypatch.setattr(chip_smoke, "N_TAKE", 300)
    monkeypatch.setattr(chip_smoke, "N_BATCH", 3)
    lines = []
    got = chip_smoke.smoke(rows=3000, seed=1, say=lines.append)
    assert got["recall"] >= chip_smoke.RECALL_MIN
    text = "\n".join(lines)
    assert text.count("bit-identical") == 3
    assert "decode.fallback counters: none" in text
    json.dumps(got)


def test_compile_cache_dir(monkeypatch):
    """The cache goes where JAX_COMPILATION_CACHE_DIR says, and nothing else
    is set; without it, to ``.jax_cache/`` at the root of the checkout."""
    import jax

    from repro import runtime

    calls = {}
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: calls.__setitem__(name, value))
    monkeypatch.setenv(runtime.CACHE_ENV, "/elsewhere/cache")
    assert runtime.enable_compile_cache() == "/elsewhere/cache"
    assert "jax_compilation_cache_dir" not in calls
    monkeypatch.delenv(runtime.CACHE_ENV)
    assert runtime.enable_compile_cache() == str(ROOT / ".jax_cache")
    assert calls["jax_compilation_cache_dir"] == str(ROOT / ".jax_cache")

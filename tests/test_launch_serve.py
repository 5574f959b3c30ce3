"""The serving entry point and the chip smoke script, at smoke size on CPU
(Pallas kernels in interpret mode)."""
import os
import shutil
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
sys.path.insert(0, ROOT)

SMOKE_ARGV = ["--smoke", "--page-slots", "8", "--slots", "2",
              "--max-len", "48", "--requests", "3", "--prompt-len", "12",
              "--max-new", "3"]


@pytest.fixture
def cache_env(monkeypatch, tmp_path):
    """A cache directory in the environment makes the cache helper a
    no-op, so these tests leave JAX's global config alone."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jc"))


@pytest.mark.parametrize("layout,kernel,impl", [
    ("batch", "auto", None), ("paged", "composed", "composed"),
    ("pooled", "fused", "fused"), ("pooled", "auto", "composed")])
def test_serve_main_returns_report(cache_env, layout, kernel, impl):
    from repro.launch import serve
    report = serve.main(SMOKE_ARGV + ["--kv-layout", layout,
                                      "--paged-kernel", kernel])
    assert report["completed"] == 3
    assert report["new_tokens"] == 9
    assert report["paged_impl"] == impl
    assert sorted(report["outputs"]) == [0, 1, 2]


def test_compile_cache_dir(monkeypatch, tmp_path):
    from repro.launch.compile_cache import REPO_CACHE_DIR, enable_compile_cache
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    try:
        assert enable_compile_cache() == str(REPO_CACHE_DIR)
        assert jax.config.jax_compilation_cache_dir == str(REPO_CACHE_DIR)
        assert REPO_CACHE_DIR == Path(ROOT, ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    with open(os.path.join(ROOT, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


def test_chip_smoke_phases_on_cpu(cache_env, capsys):
    """The smoke script's checks at smoke size: fused against composed
    first-step logits, every request served, the shared prefix shared."""
    import chip_smoke as cs
    argv = ["--smoke", "--kv-layout", "pooled", "--page-slots", "8",
            "--paged-kernel", "fused", "--slots", "4", "--max-len", "64",
            "--max-new", "3", "--seed", "0"]

    def requests():
        return cs.make_requests(0, 512, n=3, prompt_len=(12, 20),
                                shared_prefix=8, page_slots=8, max_new=3)

    cs.run_one_chip(argv, requests, on_tpu=False)
    out = capsys.readouterr().out
    assert "served 3 requests, 9 new tokens, 8 shared prompt tokens" in out


def test_chip_smoke_four_chip_path_on_cpu(tmp_path):
    """The four-chip path on a 4-device CPU mesh: the pool spread 1/4 per
    device, sharded first-step logits against device 0 alone, and each
    side's prefilled requests served to completion on its engine."""
    body = textwrap.dedent("""
        import chip_smoke as cs
        argv = ["--smoke", "--kv-layout", "pooled", "--page-slots", "8",
                "--paged-kernel", "fused", "--slots", "4", "--max-len",
                "64", "--max-new", "3", "--seed", "0"]
        cs.run_four_chips(argv, lambda: cs.make_requests(
            0, 512, n=3, prompt_len=(12, 20), shared_prefix=8,
            page_slots=8, max_new=3), on_tpu=False)
        print("FOUR_OK")
        """)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jc"),
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run([sys.executable, "-c", body], cwd=ROOT, env=env,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = proc.stdout
    assert "FOUR_OK" in out
    assert "[4 chips] served 3 requests, 9 new tokens" in out
    assert "[device 0] served 3 requests, 9 new tokens" in out
    assert "match device 0: 3 of 3" in out


@pytest.mark.parametrize("alone", [False, True])
def test_chip_smoke_fails_without_tpu(tmp_path, alone):
    script = os.path.join(ROOT, "chip_smoke.py")
    if alone:
        script = shutil.copy(script, tmp_path)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    proc = subprocess.run([sys.executable, script], cwd=tmp_path, env=env,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout

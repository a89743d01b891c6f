"""Entry-point plumbing: the persistent compilation cache and the bench
harness's exit status."""
import os
import subprocess
import sys

import pytest

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), os.pardir))

_CACHE_PROBE = """
import jax, jax.numpy as jnp
from repro.compile_cache import enable_compile_cache
path = enable_compile_cache()
assert jax.config.jax_compilation_cache_dir == path, path
if {compile}:
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.jit(lambda x: jnp.sin(x) * 3.0)(jnp.arange(8.0)).block_until_ready()
print(path)
"""


def _cache_probe(env_dir, compile_):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = env_dir
    out = subprocess.run([sys.executable, "-c",
                          _CACHE_PROBE.format(compile=compile_)],
                         capture_output=True, text=True, env=env, timeout=120)
    assert out.returncode == 0, out.stderr
    return out.stdout.strip().splitlines()[-1]


def test_compile_cache_writes_where_the_env_says(tmp_path):
    cache = str(tmp_path / "cache")
    assert _cache_probe(cache, True) == cache
    assert os.listdir(cache), "nothing was written to the cache directory"


def test_compile_cache_defaults_to_the_checkout():
    assert _cache_probe(None, False) == os.path.join(REPO, ".jax_cache")


def test_bench_harness_exits_nonzero_when_a_suite_fails(tmp_path, monkeypatch):
    from benchmarks import kernel_bench, run
    from repro import compile_cache

    def broken(quick=False):
        raise RuntimeError("suite broke")

    monkeypatch.setattr(kernel_bench, "main", broken)
    monkeypatch.setattr(compile_cache, "enable_compile_cache", lambda: "")
    monkeypatch.setattr(run, "RESULTS", str(tmp_path))
    monkeypatch.setattr(sys, "argv", ["run", "--only", "kernels"])
    with pytest.raises(SystemExit) as exc:
        run.main()
    assert exc.value.code not in (0, None)
    assert "kernels" in str(exc.value.code)
    # the summary is still written, with the failure in it
    assert "suite broke" in (tmp_path / "bench_summary.json").read_text()

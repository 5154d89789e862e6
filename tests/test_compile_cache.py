"""The entry points' persistent compilation cache directory rule.

Each case runs in a CPU-only subprocess, so the cache never turns on in
the test process itself.
"""
import json
import os
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]

CODE = """
import json, os
import jax
from repro.launch.compile_cache import CACHE_DIR, enable_compile_cache
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
before = jax.config.jax_compilation_cache_dir
used = enable_compile_cache()
if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
    jax.jit(lambda x: x * 3 + 1)(1.0).block_until_ready()
print(json.dumps({"before": before, "used": used,
                  "cache_dir": str(CACHE_DIR)}))
"""


def _run(extra_env):
    env = {"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
           "HOME": os.environ.get("HOME", "/tmp"), "JAX_PLATFORMS": "cpu",
           **extra_env}
    out = subprocess.run([sys.executable, "-c", CODE], capture_output=True,
                         text=True, timeout=300, env=env, cwd=ROOT)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_env_dir_is_used_and_no_other(tmp_path):
    cache = tmp_path / "cc"
    res = _run({"JAX_COMPILATION_CACHE_DIR": str(cache)})
    assert res["before"] == res["used"] == str(cache)
    assert any(cache.iterdir())          # the compile landed there


def test_default_dir_is_fixed_inside_the_checkout_and_ignored():
    res = _run({})
    assert res["before"] is None
    assert res["used"] == res["cache_dir"] == str(ROOT / ".jax_cache")
    ignored = (ROOT / ".gitignore").read_text().splitlines()
    assert ".jax_cache/" in ignored

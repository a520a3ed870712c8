"""Persistent compile-cache placement (openr_tpu/utils/compile_cache.py):
JAX_COMPILATION_CACHE_DIR set -> honoured, and the repo sets nothing else;
unset -> the one fixed in-checkout path, identical from any process and
any working directory, and a second process loads what the first wrote."""

import json
import os
import subprocess
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent

_CHILD = """
import json, sys
from openr_tpu.utils.compile_cache import (
    ensure_compile_cache, persistent_cache_counts,
)
returned = ensure_compile_cache()
import jax, jax.numpy as jnp
jax.jit(lambda x: (x * 3 + 1).sum())(jnp.arange(1024.0)).block_until_ready()
print(json.dumps({
    "returned": returned,
    "jax_dir": jax.config.jax_compilation_cache_dir,
    **persistent_cache_counts(),
}))
"""


def _run_child(tmp_path, cache_env=None):
    env = dict(os.environ)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if cache_env is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(cache_env)
    env.update(
        {
            "JAX_PLATFORMS": "cpu",
            "PYTHONPATH": str(REPO),
            # cache even this sub-second compile, so hits are observable
            "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0",
            "JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES": "-1",
        }
    )
    proc = subprocess.run(
        [sys.executable, "-c", _CHILD],
        env=env,
        cwd=tmp_path,  # never the checkout: the path must not follow cwd
        capture_output=True,
        text=True,
        timeout=300,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_env_var_is_honoured_and_nothing_else_is_set(tmp_path):
    cache = tmp_path / "x"
    out = _run_child(tmp_path, cache_env=cache)
    assert out["returned"] == str(cache)
    assert out["jax_dir"] == str(cache)  # JAX read the variable itself
    assert out["misses"] >= 1 and os.listdir(cache)
    again = _run_child(tmp_path, cache_env=cache)
    assert again["hits"] >= 1 and again["misses"] == 0


def test_unset_uses_the_fixed_in_checkout_path(tmp_path):
    other = tmp_path / "elsewhere"
    other.mkdir()
    first = _run_child(tmp_path)
    second = _run_child(other)  # another process, another cwd
    fixed = str(REPO / ".jax_cache")
    assert first["returned"] == first["jax_dir"] == fixed
    assert second["returned"] == second["jax_dir"] == fixed
    assert second["hits"] >= 1  # it loaded what the first process wrote


def test_no_other_code_sets_a_cache_directory():
    hits = []
    for path in REPO.rglob("*.py"):
        rel = path.relative_to(REPO)
        if rel.parts[0] in ("tests", "_chipcheck", ".jax_cache") or rel == Path(
            "openr_tpu/utils/compile_cache.py"
        ):
            continue
        text = path.read_text()
        if "jax_compilation_cache_dir" in text or "set_cache_dir" in text:
            hits.append(str(rel))
    assert hits == []

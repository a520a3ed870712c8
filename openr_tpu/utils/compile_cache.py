"""Where JAX keeps compiled programs between processes.

Every composition root (OpenrDaemon, chipbench/run.py, chip_smoke.py,
__graft_entry__.py) calls `ensure_compile_cache()` before its first
compile. The directory is decided in exactly one way:

  - `JAX_COMPILATION_CACHE_DIR` set: JAX reads the variable itself; this
    module touches nothing and no other code in the repo sets a directory.
  - unset: one fixed path inside the checkout, `<repo>/.jax_cache`
    (git-ignored). The path is part of JAX's cache key, so it is never a
    tempfile, pid or timestamp path — a directory that moves never hits.

JAX's own thresholds decide what is written (programs that took under a
second to compile are not). `persistent_cache_counts()` reads JAX's
monitoring events, so a second process can show that it loaded programs
instead of compiling them.
"""

from __future__ import annotations

import os
from typing import Dict

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
REPO_CACHE_DIR = os.path.join(
    os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    ),
    ".jax_cache",
)

_EVENTS = {
    "/jax/compilation_cache/compile_requests_use_cache": "requests",
    "/jax/compilation_cache/cache_hits": "hits",
    "/jax/compilation_cache/cache_misses": "misses",
}
_counts: Dict[str, int] = {}


def _on_event(event: str, **_kwargs) -> None:
    name = _EVENTS.get(event)
    if name is not None:
        _counts[name] += 1


def ensure_compile_cache() -> str:
    """Place the persistent compilation cache; returns the directory in
    use. Idempotent; call before the first compile."""
    import jax

    if not _counts:
        _counts.update(dict.fromkeys(_EVENTS.values(), 0))
        jax.monitoring.register_event_listener(_on_event)
    env_dir = os.environ.get(ENV_VAR)
    if env_dir:
        return env_dir
    jax.config.update("jax_compilation_cache_dir", REPO_CACHE_DIR)
    return REPO_CACHE_DIR


def persistent_cache_counts() -> Dict[str, int]:
    """This process's persistent-cache traffic since
    `ensure_compile_cache()`: compile requests that consulted the cache,
    programs loaded from it (hits) and programs written to it (misses)."""
    return dict(_counts)

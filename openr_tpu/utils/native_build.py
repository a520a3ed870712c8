"""Build one artifact of native/ into openr_tpu/_native/ through `make`.

The ctypes loaders call this on first use instead of trusting a binary
that happens to lie on disk: openr_tpu/_native/ is git-ignored, so a
checkout can carry a stale `.so` from another tree. `make` compares it
with its sources and is a no-op when it is fresh.
"""

from __future__ import annotations

import os
import subprocess

_REPO = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
_MAKE_DIR = os.path.join(_REPO, "native")
_OUT_DIR = os.path.join(_REPO, "openr_tpu", "_native")


def build_native(artifact: str) -> str:
    """`make` one target (e.g. "libopenr_spf.so") and return its path.
    Only that target is built: a failure in an unrelated native component
    (netlink needs linux headers) must not take this one down. Raises
    when the toolchain is missing or the build fails."""
    subprocess.run(
        ["make", "-C", _MAKE_DIR, f"../openr_tpu/_native/{artifact}"],
        check=True,
        capture_output=True,
        timeout=300,
    )
    return os.path.join(_OUT_DIR, artifact)

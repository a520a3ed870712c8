"""Build metadata, the common/BuildInfo equivalent.

The reference exposes build user/time/package through fb303's getBuildInfo
(openr/common/BuildInfo.h via exportBuildInfo); here the same shape is
assembled from the package itself so `breeze openr version` and the ctrl
API report something meaningful in a from-source deployment.
"""

from __future__ import annotations

import platform
import sys
from typing import Dict

VERSION = "1.0.0"  # single source of truth; breeze derives its banner from it
PACKAGE = "openr-tpu"

# SOAK_r* artifact field contract: bump when the shape of the
# judged report changes, so offline renderers (`breeze perf
# soak-report`, `breeze fleet report`) can warn instead of misreading
ARTIFACT_SCHEMA_VERSION = 1


def build_fingerprint() -> str:
    """`git describe --always --dirty` of the source tree, degrading to
    the package VERSION outside a checkout — stamped next to
    ARTIFACT_SCHEMA_VERSION in every soak artifact so a report
    line is always traceable to the exact code that produced it."""
    import os
    import subprocess

    root = os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    )
    try:
        probe = subprocess.run(
            ["git", "describe", "--always", "--dirty"],
            capture_output=True,
            cwd=root,
            timeout=10,
        )
        desc = probe.stdout.decode(errors="replace").strip()
        if probe.returncode == 0 and desc:
            return desc
    except Exception:
        pass
    return VERSION


def get_build_info() -> Dict[str, str]:
    info = {
        "build_package_name": PACKAGE,
        "build_package_version": VERSION,
        "build_mode": "opt",
        "build_platform": platform.platform(),
        "build_python": sys.version.split()[0],
        "build_rule": "openr_tpu",
    }
    info.update(get_analysis_build_info())
    return info


def get_analysis_build_info() -> Dict[str, str]:
    """Which static-analysis invariants this binary was linted against
    (the getAnalysisVersion surface: rides ctrl getBuildInfo and `breeze
    openr version`, so deployed daemons self-report their lint contract —
    docs/Analysis.md). When an analysis ran in this process (the tier-1
    self-run, a `--changed` pre-commit pass, an operator-triggered run),
    its cost is surfaced too: total wall time plus per-rule
    `<rule>=<findings>:<ms>` pairs — analysis cost is observable like
    every other cost in this codebase."""
    from openr_tpu.analysis import get_analysis_info

    meta = get_analysis_info()
    info = {
        "build_analysis_version": meta["analysis_version"],
        "build_analysis_rules": ",".join(meta["analysis_rules"]),
    }
    if "analysis_wall_ms" in meta:
        info["build_analysis_wall_ms"] = f"{meta['analysis_wall_ms']:.1f}"
        info["build_analysis_files"] = str(meta["analysis_files"])
        info["build_analysis_rule_stats"] = ",".join(
            f"{name}={stats['findings']}:{stats['ms']:.1f}ms"
            for name, stats in sorted(
                meta["analysis_rule_stats"].items()
            )
        )
    if "analysis_contracts" in meta:
        # ShapeFlow pass shape: how many @shape_contract annotations were
        # verified, how many functions were interpreted/inferred, and the
        # pass wall time — `contracts=12,functions=41,inferred=29:83.0ms`
        sf = meta["analysis_contracts"]
        info["build_analysis_contracts"] = (
            f"contracts={sf['contracts']},functions={sf['functions']},"
            f"inferred={sf['inferred']}:{sf['wall_ms']:.1f}ms"
        )
    return info

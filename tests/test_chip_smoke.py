"""chip_smoke.py off the chip: the default invocation refuses before doing
any work, the script alone (nothing else of the repo beside it) fails, and
the CPU rehearsal drives every stage end to end while saying, in so many
words, that it is not a chip run."""

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
SMOKE = REPO / "chip_smoke.py"


def _run(args, cwd, script=SMOKE, timeout=600):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"  # explicit: this child never takes a chip
    if "xla_force_host_platform_device_count" not in env.get("XLA_FLAGS", ""):
        env["XLA_FLAGS"] = (
            env.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=8"
        ).strip()
    return subprocess.run(
        [sys.executable, str(script), *args],
        env=env,
        cwd=cwd,
        capture_output=True,
        text=True,
        timeout=timeout,
    )


def test_default_invocation_refuses_without_a_tpu(tmp_path):
    proc = _run([], tmp_path)
    assert proc.returncode == 3
    assert proc.stdout.strip() == ""  # no result of any kind
    assert "refusing to run" in proc.stderr
    assert not (tmp_path / "chiprun_out").exists()  # before any work


def test_script_alone_fails(tmp_path):
    alone = tmp_path / "chip_smoke.py"
    shutil.copy(SMOKE, alone)
    proc = _run(["--cpu-rehearsal"], tmp_path, script=alone)
    assert proc.returncode != 0
    assert "openr_tpu" in proc.stderr  # ModuleNotFoundError, not a verdict
    assert not any(
        line.startswith("{") for line in proc.stdout.splitlines()
    )


def test_cpu_rehearsal_end_to_end(tmp_path):
    proc = _run(["--cpu-rehearsal"], tmp_path)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    lines = proc.stdout.strip().splitlines()
    first = lines[0]
    for field in (
        "platform=cpu", "device_kind=", "n_devices=8", "jax=", "jaxlib=",
        "libtpu=", "compile_cache=",
    ):
        assert field in first, (field, first)
    assert "NOT a chip run" in lines[1]
    assert "FAIL" not in proc.stdout
    for marker in ("stage A passed", "stage B passed", "stage C passed"):
        assert marker in proc.stdout, marker
    verdict = json.loads(lines[-1])
    # a rehearsal carries no chip verdict: the "ok" key is a chip run's
    assert "ok" not in verdict
    assert verdict["chip_run"] is False
    assert verdict["rehearsal_passed"] is True
    assert verdict["device"] == {"platform": "cpu", "kind": "cpu", "count": 8}
    summary = json.loads((tmp_path / "chiprun_out" / "chip_smoke.json").read_text())
    assert summary["rehearsal"] is True
    assert summary["A"]["counters"]["decision.spf.incremental_solves"] >= 20

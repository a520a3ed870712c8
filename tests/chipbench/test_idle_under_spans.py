"""The metrics that read the program's stages (ISSUE 26): the
`idle_under_spans` reducer on hand-made intervals, and the traced CPU
rehearsal printing the new histogram and counter metrics (a rehearsal:
nothing here is a device number)."""

import json
import os

import pytest

from chipbench import layer_metrics, trace_reduce
from chipbench import run as bench_run
from chipbench.reducers import idle_under_spans

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
REHEARSAL = "rehearsal_fabric.metric_flaps"

# two events: the device runs 1-2 and 3-4 (solve), 4.5-5 (delta extract);
# idle between the first and the last run: 2-3 and 4-4.5, 1.5 s in all
PROGRAMS = [
    ("jit_solve(1)", 1.0, 2.0),
    ("jit_solve(1)", 3.0, 4.0),
    ("jit__delta_extract(2)", 4.5, 5.0),
]
HOST = [
    ("decision.debounce", 0.0, 0.9),  # before the first run: not counted
    ("decision.spf.phase.relax", 1.0, 2.0),  # the device is busy under it
    ("decision.delta_build", 2.0, 2.4),
    ("decision.emit", 2.4, 2.5),
    ("decision.debounce", 2.6, 2.9),
    ("decision.spf.phase.prepare", 2.9, 3.0),
    ("decision.spf.phase.delta_extract", 4.0, 5.0),
    ("process.gc", 2.1, 2.3),  # nested in the delta build: counted once
    ("PjitFunction(solve)", 2.95, 3.0),  # the runtime's span, not a stage
]


def _share(spans, complement=False):
    return idle_under_spans.idle_share_under(PROGRAMS, HOST, spans, complement)


def test_idle_time_is_split_by_the_host_spans_that_lie_over_it():
    assert _share(["decision.debounce"]) == pytest.approx(100 * 0.3 / 1.5)
    assert _share(["decision.delta_build", "decision.emit"]) == pytest.approx(100 * 0.5 / 1.5)
    # a trailing * matches a prefix: prepare 0.1 s + delta_extract 0.5 s of
    # the gap 4-4.5; relax lies over busy time and adds nothing
    assert _share(["decision.spf.phase.*"]) == pytest.approx(100 * 0.6 / 1.5)
    assert _share(["decision.spf.phase.prepare"]) == pytest.approx(100 * 0.1 / 1.5)
    # spans that overlap each other are counted once
    assert _share(["decision.delta_build", "process.gc"]) == pytest.approx(100 * 0.4 / 1.5)
    # complement: under none of the named; 2.5-2.6 is under no stage at all
    everything = ["decision.*", "process.gc"]
    assert _share(everything, complement=True) == pytest.approx(100 * 0.1 / 1.5)
    parts = [
        _share(["decision.debounce"]),
        _share(["decision.delta_build", "decision.emit"]),
        _share(["decision.spf.phase.*"]),
        _share(everything, complement=True),
    ]
    assert sum(parts) == pytest.approx(100.0)


def test_nothing_to_read_gives_no_number():
    # no device plane (a rehearsal)
    assert idle_under_spans.idle_share_under([], HOST, ["decision.*"]) is None
    # a program from before the stages existed: no span of these names
    runtime_only = [h for h in HOST if h[0].startswith("Pjit")]
    assert idle_under_spans.idle_share_under(PROGRAMS, runtime_only, ["decision.*"]) is None
    assert idle_under_spans.idle_share_under(PROGRAMS, runtime_only, ["decision.*"], True) is None
    # one run: no idle time between programs
    assert idle_under_spans.idle_share_under(PROGRAMS[:1], HOST, ["decision.*"]) is None
    # through the harness's reader, from a metric's own file
    summary = trace_reduce.TraceSummary(
        window_s=6.0, busy_s=2.5, ops=[], programs=[], host=HOST, events=[]
    )
    ctx = layer_metrics.Context(
        hists={}, counters0={}, counters1={}, n_events=2, gauges={},
        trace=summary, config={}, device_kind="cpu",
    )
    with open(os.path.join(ROOT, "chipbench", "metrics", "idle_unattributed_pct.json")) as fh:
        spec = json.load(fh)
    assert layer_metrics.read(spec, ctx) == (None, "")
    summary.programs = PROGRAMS
    value, _ = layer_metrics.read(spec, ctx)
    assert value == pytest.approx(100 * 0.1 / 1.5)


def test_the_composition_metrics_name_disjoint_spans_and_their_complement():
    """idle_in_* + idle_unattributed_pct add up to 100 only if no span is
    in two lists and the complement's list holds them all."""
    specs = {}
    for name in ("idle_unattributed_pct", "idle_in_debounce_pct",
                 "idle_in_solve_host_pct", "idle_in_route_build_pct"):
        with open(os.path.join(ROOT, "chipbench", "metrics", f"{name}.json")) as fh:
            specs[name] = json.load(fh)["source"]
    assert specs["idle_unattributed_pct"]["complement"] is True
    everything = specs.pop("idle_unattributed_pct")["spans"]
    listed = [s for source in specs.values() for s in source["spans"]]
    assert len(listed) == len(set(listed))
    assert all(idle_under_spans.matches(s, everything) for s in listed)
    assert all("complement" not in source for source in specs.values())


def test_traced_rehearsal_prints_the_stage_metrics(capsys):
    rc = bench_run.main(
        ["--workload", REHEARSAL, "--seed", str(2**31 + 126), "--seconds", "1.5",
         "--allow-cpu", "--trace", "1"]
    )
    out, _ = capsys.readouterr()
    line = json.loads(out.strip().splitlines()[-1])
    assert rc == 0 and line["correct"] is True
    got = line["metrics"]
    for name in (
        "ctrl_decode_ms.avg", "kvstore_set_ms.avg", "decision_queue_wait_ms.avg",
        "decision_ingest_ms.avg", "solve_refresh_ms.avg", "solve_prepare_ms.avg",
        "solve_h2d_ms.avg", "solve_relax_wait_ms.avg", "solve_delta_extract_ms.avg",
        "solve_mirror_patch_ms.avg", "delta_build_ms.avg", "decision_emit_ms.avg",
    ):
        assert got[name]["value"] > 0 and got[name]["unit"] == "ms", name
    # every event of the mix is a warm DeltaPath event: the two round
    # counts, the changed-column count and three extracted arrays
    assert got["device_syncs_per_event"]["value"] == 6
    # the phases tile the warm solve, and solve + delta build the route build
    phases = sum(
        got[f"solve_{p}_ms.avg"]["value"]
        for p in ("prepare", "h2d", "relax_wait", "delta_extract", "mirror_patch")
    )
    assert phases == pytest.approx(got["solve_warm_ms.avg"]["value"], rel=0.05)
    inside = (
        got["solve_refresh_ms.avg"]["value"] + got["solve_warm_ms.avg"]["value"]
        + got["delta_build_ms.avg"]["value"]
    )
    # (what is left is the counter sync around the solve: under a loaded
    # test machine it is no fixed share, so the bound is wide)
    assert 0.6 * got["route_build_delta_ms.avg"]["value"] <= inside
    assert inside <= got["route_build_delta_ms.avg"]["value"]
    # no device plane on the CPU: the idle composition is left out
    assert not [name for name in got if name.startswith("idle_")]
    # the traced run's gaps would be named by these: the host plane holds them
    assert "breakdown" in line

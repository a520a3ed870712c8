"""SOAK_SMOKE tier-1 smoke (the churn sibling of FAULT_SMOKE and
TRACE_SMOKE): a seconds-long topology-churn soak — 3-node line, one
OCS-style reconfiguration wave, one injected fault — must drive the whole
continuous-telemetry loop end to end: the judged report machinery runs,
the windowed rollup accounts for 100% of convergence events while the
deliberately tiny LogSample ring only retains a tail (the eviction-proof
invariant), every scrape parses as valid exposition with full registry
coverage, and the verdict block carries every check."""

import pytest

from openr_tpu.testing.decision_harness import run_flap_batch
from openr_tpu.testing.soak import SoakConfig, run_soak, run_soak_smoke


def test_soak_smoke():
    report = run_soak_smoke()
    # the assertions live inside run_soak_smoke (shared with the driver
    # dry-run); re-pin the headline evidence here so a future refactor
    # cannot silently hollow the smoke out
    assert report["verdict"]["pass"] is True
    events = report["events"]
    assert events["total"] > report["config"]["max_event_log"]
    assert events["spans_in_rings"] < events["total"]
    assert (
        events["windowed"] + events["evicted_window_events"]
        == events["total"]
    )
    assert report["faults"]["fired"]["fib.program"] == 1
    assert len(report["waves"]) == 1 and report["waves"][0]["converged"]


def test_soak_partition_wave():
    """--partition-every wave type: one asymmetric line-edge split via
    the chaos mesh, healed after partition_hold_s — convergence must
    recover and the verdict must carry the partition checks."""
    report = run_soak(
        SoakConfig(
            nodes=3,
            waves=1,
            settle_s=0.3,
            fault_every=0,
            partition_every=1,
            partition_hold_s=0.3,
            seed=5,
            window_s=0.5,
        )
    )
    wave = report["waves"][0]
    assert len(wave["partitioned"]) == 1 and "->" in wave["partitioned"][0]
    assert wave["converged"] is True
    checks = report["verdict"]["checks"]
    assert checks["partitions_recovered"]["ok"] is True
    assert "1/1 partition wave(s)" in checks["partitions_recovered"]["detail"]
    assert checks["flood_health_attributed"]["ok"] is True
    # the partition interval is recorded as a fault interval, so any
    # p95 effect inside it is attributed, never a clean trend break
    assert len(report["faults"]["intervals"]) == 1
    assert report["verdict"]["pass"] is True


@pytest.mark.parametrize("codec", ["json", "binary", "mixed"])
def test_flap_batch_under_subscribers(codec):
    """The soak round's scale leg at toy size: 4 nodes, 1 flap, 5 socket
    subscribers (the first one stalled) and 6 in-process ones, fleet
    observer attached."""
    summary = run_flap_batch(
        nodes=4,
        flaps=1,
        subscribers=5,
        inproc_subscribers=6,
        codec=codec,
        churn_keys=2,
        churn_value_bytes=256,
    )
    assert summary["nodes"] == 4 and summary["flaps"] == 1
    assert summary["stream_codec"] == codec
    # every node closed convergence spans for the flap
    assert summary["spans_total"] >= 4
    assert 0.0 < summary["e2e_p50_ms"] <= summary["e2e_p95_ms"]
    # both cohorts were served inside the flap window
    assert summary["stream_deliveries"] > 0
    assert summary["stream_deltas"] > 0
    assert summary["stream_inproc_subscribers"] == 6
    assert summary["stream_inproc_frames"] > 0
    # bytes were encoded once per filter class and reused by its members
    assert summary["stream_encode_classes"] > 0
    assert summary["stream_encode_class_hits"] > 0
    # the stalled subscriber got its snapshot, then was throttled
    assert "snapshot" in summary["stream_stalled_kinds"]
    # the observer scraped and judged; backpressure, if it fired at all,
    # fired on the stalled subscriber's node and nowhere else
    assert summary["fleet_scrapes"] > 0 and summary["fleet_ticks"] > 0
    backpressure = summary["fleet_findings_by_kind"].get(
        "stream_backpressure", []
    )
    assert backpressure in ([], ["n0"]), summary["fleet_findings_by_kind"]

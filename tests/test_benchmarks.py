"""Smoke tests: every benchmark module runs end-to-end at tiny sizes and
prints parseable JSON result lines (the contract bench.py also follows)."""

import json
import os

import pytest


def run_and_parse(capsys, main, env, monkeypatch):
    for k, v in env.items():
        monkeypatch.setenv(k, v)
    main([])
    out = capsys.readouterr().out.strip().splitlines()
    assert out, "no JSON lines emitted"
    results = [json.loads(line) for line in out]
    for r in results:
        assert {"metric", "value", "unit", "vs_baseline"} <= set(r)
        assert isinstance(r["value"], (int, float))
    return results


def test_decision_bench(capsys, monkeypatch):
    from benchmarks.decision_bench import main

    results = run_and_parse(
        capsys,
        main,
        {
            "DECISION_GRID_SIDES": "3",
            "DECISION_FABRIC_PODS": "1",
            "DECISION_KSP2_SIDES": "3",
            "DECISION_EVENTS": "2",
            "DECISION_KSP2_PREFIXES": "3",
        },
        monkeypatch,
    )
    assert len(results) == 3


def test_kvstore_bench(capsys, monkeypatch):
    from benchmarks.kvstore_bench import main

    results = run_and_parse(
        capsys,
        main,
        {
            "KVSTORE_MERGE_SIZES": "50:10",
            "KVSTORE_DUMP_SIZES": "50",
        },
        monkeypatch,
    )
    assert len(results) == 2
    assert all(r["value"] > 0 for r in results)


def test_scale_bench(capsys, monkeypatch):
    from benchmarks.scale_bench import main

    results = run_and_parse(
        capsys,
        main,
        {
            "SCALE_CLOS_PODS": "1",
            "SCALE_WAN_N": "64",
            "SCALE_KSP_N": "64",
            "SCALE_SOURCES": "8",
            "SCALE_METRICS": "2",
        },
        monkeypatch,
    )
    assert len(results) == 4


def test_fib_bench(capsys, monkeypatch):
    from benchmarks.fib_bench import main

    results = run_and_parse(
        capsys, main, {"FIB_ROUTES": "400", "FIB_BATCH": "100"}, monkeypatch
    )
    assert results[0]["metric"] == "fib_program_routes_per_sec"


def test_incremental_bench(capsys, monkeypatch):
    from benchmarks.incremental_bench import main

    results = run_and_parse(
        capsys,
        main,
        {
            "INC_PODS": "2",
            "INC_PLANES": "2",
            "INC_SSW": "2",
            "INC_FSW": "2",
            "INC_RSW": "4",
            "INC_EVENTS": "6",
        },
        monkeypatch,
    )
    r = results[0]
    # the warm-start win must be visible in relaxation round counts, the
    # hardware-independent half of the metric (the bench asserts this too)
    assert r["rounds_warm_mean"] < r["rounds_cold_mean"]
    assert r["p99_ms"] > 0
    assert r["baseline"] == "cold-solve"


def test_bench_py_smoke(capsys, monkeypatch):
    """`python bench.py` end-to-end under BENCH_SMOKE=1: tiny topology,
    reps 1/8 — bench bitrot fails tier-1 instead of zeroing BENCH rounds.
    Every stdout line must be parseable JSON: the SPF/s headline, the
    p95 hello-to-programmed-route convergence line from the emulator flap
    run (the ROADMAP 'second bench metric line'), and the what-if TE
    optimization line (ISSUE 7 'third metric line')."""
    import bench

    monkeypatch.setenv("BENCH_SMOKE", "1")
    monkeypatch.setenv("BENCH_CONV_NODES", "4")
    monkeypatch.setenv("BENCH_CONV_FLAPS", "1")
    bench.main([])
    out = capsys.readouterr().out.strip().splitlines()
    assert len(out) >= 10, (
        "bench.py must print SPF+convergence+TE+scale+exporter+stream+apsp"
        "+fleet+journal+loss JSON lines"
    )
    results = [json.loads(line) for line in out]
    for result in results:
        assert {"metric", "value", "unit", "vs_baseline"} <= set(result)
        assert result["value"] > 0
        # every line names the device it ran on (conftest pins the
        # 8-device CPU platform), and nothing marks a line degraded: the
        # script never picks a backend
        assert result["platform"] == "cpu"
        assert result["device_kind"]
        assert result["n_devices"] == 8
        assert "backend" not in result
        assert "degraded" not in result
        # artifact provenance stamp (ISSUE 17): every line is traceable
        # to the exact code + field contract that produced it
        assert result["schema_version"] >= 1
        assert result["build"]
    assert results[0]["metric"].endswith("spf_recomputes_per_sec")
    # device-memory columns (docs/Monitoring.md "Device-memory
    # observatory"): the SPF, TE, scale-tiled and APSP lines each report
    # the ledger's peak resident bytes for the line's working set next to
    # the predict_fit forward model — the delta column is the standing
    # record of how tight the admission arithmetic tracks reality
    for idx in (0, 2, 3, 6):
        line = results[idx]
        assert line["mem_peak_bytes"] > 0, line["metric"]
        assert line["mem_predicted_bytes"] > 0, line["metric"]
        assert line["mem_predicted_vs_live_bytes"] == (
            line["mem_predicted_bytes"] - line["mem_peak_bytes"]
        ), line["metric"]
    # phase-split contract (ISSUE 13): the SPF line carries per-phase
    # attribution columns measured with explicit barriers, so the first
    # hardware round lands with h2d/relax/d2h split out of the headline
    spf_phases = results[0]["phases"]
    assert set(spf_phases) == {"h2d_ms", "relax_ms", "d2h_ms"}
    for value in spf_phases.values():
        assert value >= 0.0
    assert spf_phases["relax_ms"] > 0.0
    assert results[1]["metric"] == "convergence_e2e_p95_ms"
    assert results[1]["spans"] > 0
    assert results[2]["metric"] == "te_optimize_ms"
    assert results[2]["initial_max_util"] >= results[2]["optimized_max_util"]
    # the destination-tiled scale line: per-device tile bytes must sit a
    # full graph-axis factor under the replica bytes it replaces
    scale = results[3]
    assert scale["metric"].startswith("scale")
    assert scale["metric"].endswith("_tiled_cold_solve_ms")
    assert scale["warm_flap_ms"] > 0
    b_ax, g_ax = scale["mesh"]
    assert (
        scale["tile_bytes_per_device"] * b_ax * g_ax
        == scale["replica_bytes_per_device"]
    )
    # the scale line's phase split (warm flap event under barriers; halo
    # traffic rides inside relax, split by the rounds gauges)
    scale_phases = scale["phases"]
    assert set(scale_phases) == {"h2d_ms", "relax_ms", "d2h_ms"}
    assert scale_phases["relax_ms"] > 0.0
    # the exporter-overhead line (continuous-telemetry cost on the same
    # flap batch as the convergence line): a parse-validated render and a
    # measured per-span rollup fold cost must both be present and nonzero
    exporter = results[4]
    assert exporter["metric"] == "exporter_scrape_render_ms"
    assert exporter["rollup_record_us"] > 0
    assert exporter["metrics_series"] > 0
    # the streaming fan-out line (ISSUE 11 'sixth metric line'): sustained
    # delta-delivery rate across concurrent subscribeKvStore subscribers
    # on the flap batch, with the convergence p95 of the subscriber run
    # reported next to the zero-subscriber baseline (bench.py asserts the
    # held-flat envelope itself; the contract here pins the line's shape)
    stream = results[5]
    assert stream["metric"] == "stream_fanout_events_s"
    assert stream["subscribers"] > 0
    assert stream["deliveries"] > 0
    assert stream["value"] > 0
    assert stream["e2e_p95_ms"] > 0
    assert stream["baseline_e2e_p95_ms"] > 0
    # shared-encode columns (ISSUE 16): the encode-share meter and the
    # class-level sharing evidence ride the line, plus the subscriber
    # sweep (BENCH_STREAM_SWEEP; the smoke env pins one extra point)
    assert 0.0 <= stream["encode_share"] < 1.0
    assert stream["encode_classes"] > 0
    assert 0.0 <= stream["class_hit_rate"] <= 1.0
    assert isinstance(stream["sweep"], list) and stream["sweep"]
    for point in stream["sweep"]:
        assert point["subscribers"] > 0
        assert point["events_s"] > 0
        assert 0.0 <= point["encode_share"] < 1.0
        assert 0.0 <= point["class_hit_rate"] <= 1.0
    # the blocked-FW APSP line (ISSUE 12 'seventh metric line'): cold
    # close plus the warm re-close of a single-link event and the
    # FW-vs-batched-Dijkstra crossover sweep; the warm path must report
    # its restricted re-close rounds (the O(dirty-blocks) machinery ran)
    apsp = results[6]
    assert apsp["metric"] == "fw_apsp_close_ms"
    assert apsp["warm_reclose_ms"] > 0
    assert apsp["reclose_rounds"] >= 1
    assert len(apsp["crossover"]) >= 2
    for point in apsp["crossover"]:
        assert point["fw_close_ms"] > 0
        assert point["batched_dijkstra_ms"] > 0
    # the fleet-observation line (ISSUE 15 'eighth metric line'): the
    # flap batch re-run with the fleet observer attached over real ctrl
    # sockets — mean SLO-watchdog tick cost, with the attached run's
    # convergence p95 next to the detached baseline's (bench.py asserts
    # the held-flat envelope itself; the contract here pins the shape)
    fleet = results[7]
    assert fleet["metric"] == "fleet_watch_overhead_ms"
    assert fleet["value"] > 0
    assert fleet["fleet_ticks"] > 0
    assert fleet["fleet_scrapes"] > 0
    assert fleet["attached_e2e_p95_ms"] > 0
    assert fleet["baseline_e2e_p95_ms"] > 0
    # the journal-recording line (ISSUE 17 'ninth metric line'): the flap
    # batch re-run with every node journaling publications + RIB deltas —
    # mean sampled per-record cost, replay-verified on every node against
    # the CPU oracle, with the journal-on run's convergence p95 next to
    # the journal-off baseline's (bench.py asserts the held-flat envelope
    # and full verification itself; the contract here pins the shape)
    journal = results[8]
    assert journal["metric"] == "journal_record_us"
    assert journal["value"] > 0
    assert journal["journal_records"] > 0
    assert journal["journal_nodes"] > 0
    assert journal["journal_replay_verified"] == journal["journal_nodes"]
    assert journal["attached_e2e_p95_ms"] > 0
    assert journal["baseline_e2e_p95_ms"] > 0
    # the convergence-under-loss line (ISSUE 18 'tenth metric line'): the
    # flap batch re-run behind a seeded chaos mesh dropping KvStore RPCs —
    # the dissemination plane must still converge, and the dropped-RPC
    # count proves the mesh actually interfered (bench.py asserts the
    # bounded-degradation envelope itself; the contract pins the shape)
    loss = results[9]
    assert loss["metric"] == "convergence_under_loss_p95_ms"
    assert loss["value"] > 0
    assert loss["chaos_loss"] > 0
    assert loss["chaos_kv_dropped"] >= 0
    assert loss["spans"] > 0
    assert loss["clean_e2e_p95_ms"] > 0


def test_bench_py_failure_prints_no_line_and_raises(capsys, monkeypatch):
    """A bench line that fails must not be summarized away: the exception
    propagates out of main() and NO line is printed — not the failed one,
    and not the lines that had already been measured (a partial round
    under device metric names is not a round)."""
    import bench

    def boom():
        raise RuntimeError("UNAVAILABLE: TPU backend setup/compile error")

    monkeypatch.setenv("BENCH_SMOKE", "1")
    monkeypatch.setenv("BENCH_CONVERGENCE", "0")
    monkeypatch.setenv("BENCH_SCALE", "0")
    monkeypatch.setenv("BENCH_APSP", "0")
    monkeypatch.setattr(bench, "_bench_te", boom)
    with pytest.raises(RuntimeError, match="UNAVAILABLE"):
        bench.main([])
    assert capsys.readouterr().out.strip() == ""


def test_bench_py_dead_backend_exits_nonzero_without_output():
    """The BENCH_r02–r05 failure mode: the configured JAX backend cannot
    initialize. The script must not choose another one — it exits
    non-zero before printing a single line under a device metric's
    name."""
    import subprocess
    import sys as _sys
    from pathlib import Path

    env = dict(os.environ)
    env.update(
        {
            # a platform that has no plugin here: jax.devices() raises
            "JAX_PLATFORMS": "no_such_backend",
            "BENCH_SMOKE": "1",
        }
    )
    bench_path = Path(__file__).resolve().parent.parent / "bench.py"
    proc = subprocess.run(
        [_sys.executable, str(bench_path)],
        env=env,
        capture_output=True,
        timeout=300,
        text=True,
    )
    assert proc.returncode != 0, proc.stdout[-2000:]
    assert proc.stdout.strip() == "", proc.stdout[-2000:]
    assert "no_such_backend" in proc.stderr


def test_config_store_bench(capsys, monkeypatch):
    from benchmarks.config_store_bench import main

    results = run_and_parse(
        capsys, main, {"CS_KEYS": "50", "CS_VALUE_BYTES": "64"}, monkeypatch
    )
    assert results[0]["metric"] == "config_store_writes_per_sec"

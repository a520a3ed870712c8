"""The stages of the served path (ISSUE 26): `monitor.spans.stage` alone,
the full-collection watch, and one warm event through a whole daemon —
ctrl write -> KvStore -> Decision -> solver phases -> DeltaPath -> Fib —
with the profiler's annotation replaced by a recorder. Every stage is
seen once, in order; no two overlap; each feeds the histogram named like
it; from Decision on they carry the event's build number."""

import asyncio
import gc
import time

import pytest

from chipbench.lsdb import AREA, Lsdb, WireEncoder
from chipbench.topologies import build_edges
from openr_tpu.config import Config
from openr_tpu.ctrl.client import CtrlClient
from openr_tpu.kvstore.transport import InProcessTransport
from openr_tpu.monitor import spans
from openr_tpu.openr import OpenrDaemon
from openr_tpu.platform import FIB_CLIENT_OPENR, MockFibHandler
from openr_tpu.spark.io_provider import MockIoNetwork

# the profiler spans of one warm DeltaPath event, in the order they run
WARM_EVENT_STAGES = [
    "ctrl.decode",
    "kvstore.set_key_vals",
    "decision.ingest",
    "decision.debounce",
    "decision.spf.phase.refresh",
    "decision.spf.phase.prepare",
    "decision.spf.phase.h2d",
    "decision.spf.phase.relax",
    "decision.spf.phase.delta_extract",
    "decision.spf.phase.mirror_patch",
    "decision.delta_build",
    "decision.emit",
    "fib.apply",
    "fib.program",
]
# the stretches that are histograms only: an umbrella or a queue hop
HISTOGRAM_ONLY = [
    "decision.queue_wait_ms",
    "decision.debounce_ms",
    "fib.queue_wait_ms",
    "fib.program_ms",
    "convergence.unstaged_ms",
]


class RecordedAnnotation:
    """Stands in for jax.profiler.TraceAnnotation: (what, name, args, t)."""

    log = []

    def __init__(self, name, **kwargs):
        self.name, self.kwargs = name, kwargs

    def __enter__(self):
        self.log.append(("enter", self.name, self.kwargs, time.perf_counter()))

    def __exit__(self, *exc):
        self.log.append(("exit", self.name, self.kwargs, time.perf_counter()))


@pytest.fixture
def annotations(monkeypatch):
    RecordedAnnotation.log = []
    monkeypatch.setattr(spans, "TraceAnnotation", RecordedAnnotation)
    return RecordedAnnotation.log


def test_stage_records_its_histogram_and_enters_an_annotation_of_its_name(annotations):
    hists = {}
    with spans.stage("decision.ingest", hists, build=12) as st:
        time.sleep(0.002)
    assert st.ms >= 2.0
    assert list(hists) == ["decision.ingest_ms"]  # the span's name + _ms
    assert hists["decision.ingest_ms"].count == 1
    assert hists["decision.ingest_ms"].sum == pytest.approx(st.ms)
    assert [(what, name, args) for what, name, args, _ in annotations] == [
        ("enter", "decision.ingest", {"build": 12}),
        ("exit", "decision.ingest", {"build": 12}),
    ]
    # no histograms: the annotation alone, and no build: no argument
    waited = spans.stage("decision.debounce").start()
    assert waited.stop() >= 0.0 and hists.keys() == {"decision.ingest_ms"}
    assert annotations[-1][:3] == ("exit", "decision.debounce", {})


def test_gc_watch_times_full_collections_while_a_daemon_holds_it(annotations):
    watch = spans.GcWatch()
    before = list(gc.callbacks)
    first, second = object(), object()  # two daemons of one process
    watch.acquire(first)
    watch.acquire(second)
    gc.collect(0)  # a young collection is not a pause worth a stage
    assert not watch.histograms
    gc.collect()
    assert watch.histograms["process.gc_ms"].count == 1
    assert [e[:2] for e in annotations] == [("enter", "process.gc"), ("exit", "process.gc")]
    watch.release(first)
    watch.release(first)  # not its holder any more: nothing to undo
    assert len(gc.callbacks) == len(before) + 1  # the other still runs
    watch.release(second)
    assert gc.callbacks == before


TOPOLOGY = {
    "generator": "fabric",
    "args": {"pods": 3, "ssw_per_plane": 2, "fsw_per_pod": 4, "rsw_per_pod": 6},
}
ME = "rsw0_0"


async def _warm_event_log(annotations):
    """Loads the toy fabric through ctrl, sends three metric moves of a
    far link, and returns what the third left: (annotation log, histogram
    dict of the event, the event's build number)."""
    lsdb = Lsdb(build_edges(TOPOLOGY))
    wire = WireEncoder(lsdb)
    agent = MockFibHandler()
    daemon = OpenrDaemon(
        Config.from_dict(
            {
                "node_name": ME,
                "dryrun": False,
                "decision_config": {"solver_backend": "tpu"},
            }
        ),
        io_provider=MockIoNetwork().provider(ME),
        kv_transport=InProcessTransport(),
        fib_service=agent,
        ctrl_port=0,
    )
    port = await daemon.start()
    dcount, fcount = daemon.decision.counters, daemon.fib.counters

    async def settled(done):
        deadline = time.monotonic() + 60.0
        while not done():
            assert time.monotonic() < deadline, "event not programmed"
            await asyncio.sleep(0.002)

    try:
        async with CtrlClient(port=port) as client:
            own = f"adj:{ME}"
            keys = [k for k in wire.all_keys() if k != own] + [own]
            await client.call(
                "setKvStoreKeyVals", area=AREA, key_vals=wire.key_vals(keys)
            )
            # Fib's first full sync is scheduled and counts no update: the
            # load is over when the agent holds a route to every other node
            await settled(
                lambda: len(agent.unicast_routes.get(FIB_CLIENT_OPENR, {}))
                >= len(lsdb.nodes) - 1
                and fcount.get("fib.process_route_db", 0)
                == dcount.get("decision.route_updates_published", 0)
            )
            for metric in (5, 1, 7):  # each moves the ECMP set toward rsw1_2
                await client.call("getHistograms", reset=True)
                del annotations[:]
                updates = fcount.get("fib.num_of_route_updates", 0)
                runs = dcount["decision.route_build_runs"]
                changed = lsdb.set_metric("fsw1_1", "rsw1_2", metric)
                await client.call(
                    "setKvStoreKeyVals",
                    area=AREA,
                    key_vals=wire.key_vals([f"adj:{n}" for n in changed]),
                )
                await settled(
                    lambda: fcount.get("fib.num_of_route_updates", 0) > updates
                )
                assert dcount["decision.route_build_runs"] == runs + 1
            log = list(annotations)
            hists = await client.call("getHistograms")
            assert dcount["decision.route_build_delta_runs"] >= 1
            return log, hists, dcount["decision.route_build_runs"]
    finally:
        await daemon.stop()


def test_a_warm_event_leaves_every_stage_once_in_order_and_none_overlap(annotations):
    gc.disable()  # a full collection inside the event would nest in a stage
    try:
        log, hists, build = asyncio.new_event_loop().run_until_complete(
            _warm_event_log(annotations)
        )
    finally:
        gc.enable()
    # tiling: each stage is left before the next is entered
    assert [what for what, *_ in log] == ["enter", "exit"] * (len(log) // 2)
    assert [name for what, name, *_ in log if what == "enter"] == WARM_EVENT_STAGES
    assert [name for what, name, *_ in log if what == "exit"] == WARM_EVENT_STAGES
    times = [t for *_, t in log]
    assert times == sorted(times)
    # from Decision on, one event's spans share its build number
    for what, name, args, _ in log:
        if name.startswith(("ctrl.", "kvstore.")):
            assert args == {}
        else:
            assert args == {"build": build}, name
    # each stage fed the histogram named like it, once for this event
    for name in WARM_EVENT_STAGES:
        if f"{name}_ms" not in HISTOGRAM_ONLY:
            assert hists[f"{name}_ms"]["count"] == 1, name
    for name in HISTOGRAM_ONLY:
        assert hists[name]["count"] == 1, name
    # the phases inside the solve add up to the solve, and the solve and
    # the delta build to the DeltaPath route build
    phases = sum(
        hists[f"decision.spf.phase.{p}_ms"]["sum"]
        for p in ("prepare", "h2d", "relax", "delta_extract", "mirror_patch")
    )
    assert phases == pytest.approx(hists["decision.spf.solve_warm_ms"]["sum"], rel=0.05)
    route_build = hists["decision.route_build_delta_ms"]["sum"]
    inside = (
        hists["decision.spf.phase.refresh_ms"]["sum"]
        + hists["decision.spf.solve_warm_ms"]["sum"]
        + hists["decision.delta_build_ms"]["sum"]
    )
    assert 0.6 * route_build <= inside <= route_build
    # the daemon's stop took the collection hook away again
    assert spans.GC_WATCH._on_gc not in gc.callbacks

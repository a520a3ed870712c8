"""The comparison that decides `correct`.

It reads what the timed path produced, where it ends: the platform agent's
log of programming calls and its tables. It replays the window's events on
the plain LSDB copy and asks the reference that the configuration names
(its `"reference"`: a module of chipbench/, `reference` where it names
none) what the vantage had to hold after each. Every comparison is exact,
so every limit is 0.

A table is the plain form of what the agent holds. Unicast: `{prefix:
frozenset((address, interface, metric, push))}`, `push` the PUSH labels of
the next hop's MPLS action, `()` where it has none. MPLS: `{top label:
frozenset((address, interface, action, labels))}`, `labels` the PUSH
labels, the SWAP label, or `()`. `Tables` is the pair.

Numbers compared (each returned beside its limit):

  table_mismatches   prefixes of the agent's final unicast table whose
                     next-hop set differs from the reference's, either way
  mpls_table_mismatches  labels of the agent's final MPLS table whose entry
                     differs from the reference's, either way
  event_mismatches   verified events whose programmed routes differ from
                     what the event had to change: a route or label route
                     the reference changed that was not programmed (or not
                     deleted), or a programmed one that is not the
                     reference's
  events_unprogrammed  events after which nothing reached the agent though
                     the reference says a route changed
  events_not_one_update  events of the window for which Decision did not
                     publish exactly one route update (a write split over
                     several, or merged with another event's)
  served_off_device  how far fallback / breaker / failure counters moved
                     inside the window

`agent_events` is, per event of the window, the slice of the agent's log
that the event produced: a list of (call name, payload) in order. Label
calls are the configuration's to allow: with segment routing off (the
daemon's `enable_segment_routing`, off by default) an mpls call fails the
event; a full sync inside the window fails it either way.
"""

from __future__ import annotations

import random
from typing import Callable, Dict, List, Sequence, Tuple

from chipbench.reference import MplsTable, Table, Tables

LIMITS = {
    "table_mismatches": 0,
    "mpls_table_mismatches": 0,
    "event_mismatches": 0,
    "events_unprogrammed": 0,
    "events_not_one_update": 0,
    "served_off_device": 0,
}


def _labels(action) -> Tuple[str, tuple]:
    """An MPLS action -> (its name, the labels it pushes or swaps in)."""
    name = action.action.name
    if name == "PUSH":
        return name, tuple(action.push_labels)
    return name, (action.swap_label,) if name == "SWAP" else ()


def routes_as_table(routes) -> Table:
    """The program's UnicastRoute objects -> the plain form."""
    return {
        str(route.dest): frozenset(
            (nh.address, nh.iface, nh.metric,
             () if nh.mpls_action is None else _labels(nh.mpls_action)[1])
            for nh in route.nexthops
        )
        for route in routes
    }


def mpls_routes_as_table(routes) -> MplsTable:
    """The program's MplsRoute objects -> the plain form."""
    return {
        route.top_label: frozenset(
            (nh.address, nh.iface, *_labels(nh.mpls_action))
            for nh in route.nexthops
        )
        for route in routes
    }


def table_mismatches(got: dict, want: dict) -> list:
    """The keys (prefixes or labels) whose entry differs, either way."""
    return sorted(
        p for p in set(got) | set(want) if got.get(p) != want.get(p)
    )


# a programming call -> (0 unicast or 1 MPLS, whether it adds)
_DELTA_CALLS = {
    "add_unicast_routes": (0, True),
    "delete_unicast_routes": (0, False),
    "add_mpls_routes": (1, True),
    "delete_mpls_routes": (1, False),
}


def apply_calls(
    calls: Sequence[Tuple[str, list]], segment_routing: bool = False
) -> Tuple[Tables, Tuple[set, set]]:
    """One event's programming calls -> ((routes set, label routes set),
    (prefixes deleted, labels deleted)), the later call winning where two
    name the same prefix or label."""
    programmed: Tables = ({}, {})
    deleted: Tuple[set, set] = (set(), set())
    for name, payload in calls:
        if name in ("sync_fib", "sync_mpls_fib"):
            raise ValueError("a full sync inside the window: not a delta")
        if name not in _DELTA_CALLS:
            raise ValueError(f"{name}: a call that no configuration allows")
        side, adds = _DELTA_CALLS[name]
        if side and not segment_routing:
            raise ValueError(
                f"{name}: a call that a configuration with segment routing "
                "off does not allow"
            )
        if adds:
            as_table = mpls_routes_as_table if side else routes_as_table
            for key, nexthops in as_table(payload).items():
                programmed[side][key] = nexthops
                deleted[side].discard(key)
        else:
            for key in payload if side else map(str, payload):
                programmed[side].pop(key, None)
                deleted[side].add(key)
    return programmed, deleted


def event_is_wrong(
    calls: Sequence[Tuple[str, list]],
    changed: Tuple[Sequence, Sequence],
    after: Tables,
    segment_routing: bool = False,
) -> str:
    """'' when the event's programming is exactly what moves the vantage's
    tables to `after`, modulo routes re-programmed unchanged; else a short
    description of the first difference. `changed` is the prefixes and the
    labels whose route the event changes in the reference."""
    try:
        programmed, deleted = apply_calls(calls, segment_routing)
    except ValueError as exc:
        return str(exc)
    for side in (0, 1):
        want, done, gone = after[side], programmed[side], deleted[side]
        for key, nexthops in done.items():
            if want.get(key) != nexthops:
                return (
                    f"{key} programmed {sorted(nexthops, key=repr)}, "
                    f"reference {sorted(want.get(key, ()), key=repr)}"
                )
        for key in gone:
            if key in want:
                return f"{key} deleted, reference holds it"
        for key in changed[side]:
            if key in want and key not in done:
                return f"{key} changed in the reference, not programmed"
            if key not in want and key not in gone:
                return f"{key} gone in the reference, not deleted"
    return ""


def choose_events(n_events: int, verify_events: int, seed: int) -> List[int]:
    """Indices of the window's events to verify: all of them where there
    are no more than `verify_events`, else a sample drawn from the seed,
    the last event in it."""
    if n_events <= verify_events:
        return list(range(n_events))
    rng = random.Random(seed ^ 0x5EED)
    chosen = set(rng.sample(range(n_events - 1), verify_events - 1))
    chosen.add(n_events - 1)
    return sorted(chosen)


def segment_routing(config: dict) -> bool:
    """Whether the configuration's daemon programs label routes."""
    return bool(config["daemon"].get("enable_segment_routing", False))


def replay_reference(
    config: dict, params: dict, seed: int, n_warm: int, n_events: int,
    verify: List[int],
) -> Dict[int, Tables]:
    """The reference's side of a run: an LSDB of its own from the
    configuration, the mix's events replayed from the seed, and the
    vantage's tables after `i` events of the window for every `i` that the
    comparison reads (0: at the window's start)."""
    import importlib

    from chipbench.lsdb import Lsdb
    from chipbench.topologies import build_edges

    kind = importlib.import_module(f"chipbench.traffic_kinds.{params['kind']}")
    module = importlib.import_module(f"chipbench.{config.get('reference', 'reference')}")
    needed = {i for v in verify for i in (v, v + 1)} | {n_events}
    lsdb = Lsdb(build_edges(config["topology"]))
    reference = module.Reference(lsdb, config["vantage"], config)
    events = kind.generate(params, seed)
    at_index: Dict[int, Tables] = {}
    for i in range(n_warm + n_events + 1):
        if i - n_warm in needed:
            at_index[i - n_warm] = reference.tables()
        keys = next(events).apply(lsdb)
        reference.refresh(key.split(":", 1)[1] for key in keys)
    return at_index


def compare(
    *,
    final: Tables,
    agent_events: List[Sequence[Tuple[str, list]]],
    tables: Callable[[int], Tables],
    verify: List[int],
    updates_per_event: Sequence[int],
    counter_moves: Dict[str, int],
    segment_routing: bool = False,
) -> Tuple[bool, Dict[str, dict], List[str]]:
    """`final` is the agent's tables at the window's end; `tables(i)` the
    reference's after `i` events of the window (0: at the window's start);
    `updates_per_event`, how many route updates Decision published for
    each. Returns (correct, numbers beside limits, notes for standard
    error)."""
    notes: List[str] = []
    n_events = len(agent_events)
    want = tables(n_events)
    bad_prefixes = table_mismatches(final[0], want[0])
    if bad_prefixes:
        notes.append(f"final table differs at {bad_prefixes[:8]}")
    bad_labels = table_mismatches(final[1], want[1])
    if bad_labels:
        notes.append(f"final MPLS table differs at labels {bad_labels[:8]}")
    wrong = unprogrammed = 0
    for i in verify:
        before, after = tables(i), tables(i + 1)
        changed = tuple(table_mismatches(before[s], after[s]) for s in (0, 1))
        if not agent_events[i] and any(changed):
            unprogrammed += 1
            continue
        why = event_is_wrong(agent_events[i], changed, after, segment_routing)
        if why:
            wrong += 1
            if wrong <= 4:
                notes.append(f"event {i}: {why}")
    not_one = [i for i, n in enumerate(updates_per_event) if n != 1]
    if not_one:
        notes.append(f"events without exactly one route update: {not_one[:8]}")
    moved = {k: v for k, v in counter_moves.items() if v}
    if moved:
        notes.append(f"counters moved in the window: {moved}")
    got = {
        "table_mismatches": len(bad_prefixes),
        "mpls_table_mismatches": len(bad_labels),
        "event_mismatches": wrong,
        "events_unprogrammed": unprogrammed,
        "events_not_one_update": len(not_one),
        "served_off_device": sum(abs(v) for v in moved.values()),
    }
    compared = {
        name: {"value": got[name], "limit": LIMITS[name]} for name in LIMITS
    }
    correct = all(got[name] <= LIMITS[name] for name in LIMITS)
    return correct, compared, notes

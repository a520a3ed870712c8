"""The comparison that decides `correct`.

It reads what the timed path produced, where it ends: the platform agent's
log of programming calls and its table. It replays the window's events on
the plain LSDB copy and asks the reference (reference.py) what the vantage
had to hold after each. Every comparison is exact, so every limit is 0.

Numbers compared (each returned beside its limit):

  table_mismatches   prefixes of the agent's final table whose next-hop set
                     differs from the reference's, either way
  event_mismatches   verified events whose programmed routes differ from
                     what the event had to change: a route the reference
                     changed that was not programmed (or not deleted), or a
                     programmed route that is not the reference's
  events_unprogrammed  events after which nothing reached the agent though
                     the reference says a route changed
  events_not_one_update  events of the window for which Decision did not
                     publish exactly one route update (a write split over
                     several, or merged with another event's)
  served_off_device  how far fallback / breaker / failure counters moved
                     inside the window

`agent_events` is, per event of the window, the slice of the agent's log
that the event produced: a list of (call name, payload) in order.
"""

from __future__ import annotations

import random
from typing import Callable, Dict, List, Sequence, Tuple

from chipbench.reference import Table

LIMITS = {
    "table_mismatches": 0,
    "event_mismatches": 0,
    "events_unprogrammed": 0,
    "events_not_one_update": 0,
    "served_off_device": 0,
}


def routes_as_table(routes) -> Table:
    """The program's UnicastRoute objects -> the reference's plain form."""
    return {
        str(route.dest): frozenset(
            (nh.address, nh.iface, nh.metric) for nh in route.nexthops
        )
        for route in routes
    }


def table_mismatches(got: Table, want: Table) -> List[str]:
    return sorted(
        p for p in set(got) | set(want) if got.get(p) != want.get(p)
    )


def apply_calls(calls: Sequence[Tuple[str, list]]) -> Tuple[Table, set]:
    """One event's programming calls -> (routes set, prefixes deleted), the
    later call winning where two name the same prefix."""
    programmed: Table = {}
    deleted: set = set()
    for name, payload in calls:
        if name == "add_unicast_routes":
            for prefix, nexthops in routes_as_table(payload).items():
                programmed[prefix] = nexthops
                deleted.discard(prefix)
        elif name == "delete_unicast_routes":
            for prefix in map(str, payload):
                programmed.pop(prefix, None)
                deleted.add(prefix)
        elif name == "sync_fib":
            raise ValueError("a full sync inside the window: not a delta")
        else:
            # the configurations state "no mpls route is programmed"; one
            # that enables labels has to bring their comparison with it
            raise ValueError(f"{name}: a call that no configuration allows")
    return programmed, deleted


def event_is_wrong(
    calls: Sequence[Tuple[str, list]], changed: Sequence[str], after: Table
) -> str:
    """'' when the event's programming is exactly what moves the vantage's
    table to `after`, modulo routes re-programmed unchanged; else a short
    description of the first difference. `changed` is the prefixes whose
    route the event changes in the reference."""
    try:
        programmed, deleted = apply_calls(calls)
    except ValueError as exc:
        return str(exc)
    for prefix, nexthops in programmed.items():
        if after.get(prefix) != nexthops:
            return f"{prefix} programmed {sorted(nexthops)}, reference {sorted(after.get(prefix, ()))}"
    for prefix in deleted:
        if prefix in after:
            return f"{prefix} deleted, reference holds it"
    for prefix in changed:
        if prefix in after and prefix not in programmed:
            return f"{prefix} changed in the reference, not programmed"
        if prefix not in after and prefix not in deleted:
            return f"{prefix} gone in the reference, not deleted"
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


def replay_reference(
    config: dict, params: dict, seed: int, n_warm: int, n_events: int,
    verify: List[int],
) -> Dict[int, Table]:
    """The reference's side of a run: an LSDB of its own from the
    configuration, the mix's events replayed from the seed, and the
    vantage's table after `i` events of the window for every `i` that the
    comparison reads (0: at the window's start)."""
    import importlib

    from chipbench.lsdb import Lsdb
    from chipbench.reference import Reference
    from chipbench.topologies import build_edges

    kind = importlib.import_module(f"chipbench.traffic_kinds.{params['kind']}")
    needed = {i for v in verify for i in (v, v + 1)} | {n_events}
    lsdb = Lsdb(build_edges(config["topology"]))
    reference = Reference(lsdb, config["vantage"])
    events = kind.generate(params, seed)
    at_index: Dict[int, Table] = {}
    for i in range(n_warm + n_events + 1):
        if i - n_warm in needed:
            at_index[i - n_warm] = reference.table()
        keys = next(events).apply(lsdb)
        reference.refresh(key.split(":", 1)[1] for key in keys)
    return at_index


def compare(
    *,
    final_table: Table,
    agent_events: List[Sequence[Tuple[str, list]]],
    tables: Callable[[int], Table],
    verify: List[int],
    updates_per_event: Sequence[int],
    counter_moves: Dict[str, int],
) -> Tuple[bool, Dict[str, dict], List[str]]:
    """`tables(i)` is the reference's table after `i` events of the window
    (0: at the window's start); `updates_per_event`, how many route
    updates Decision published for each. Returns (correct, numbers beside
    limits, notes for standard error)."""
    notes: List[str] = []
    n_events = len(agent_events)
    bad_prefixes = table_mismatches(final_table, tables(n_events))
    if bad_prefixes:
        notes.append(f"final table differs at {bad_prefixes[:8]}")
    wrong = unprogrammed = 0
    for i in verify:
        after = tables(i + 1)
        changed = table_mismatches(tables(i), after)
        if not agent_events[i] and changed:
            unprogrammed += 1
            continue
        why = event_is_wrong(agent_events[i], changed, after)
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

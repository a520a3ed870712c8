"""The control of the comparison: the reference put in the program's
place with one stated guarantee broken. It has to come out not correct.

The system states no precision, so the control breaks a guarantee of the
configuration ("every route the agent holds equals the reference's on the
same LSDB"), in the two ways that would tempt a later change:

  stale      each event is answered from the LSDB as it was one event
             earlier (an acknowledgement before the solve has the event):
             a stale answer where a current one is promised
  first_hop  every next-hop set is cut to one member (the equal-cost
             multipath extraction left out): an approximate answer where
             an exact one is promised

    python3 -m chipbench.control --workload <cell> --seed <n> --events <n>

needs no chip and no daemon: the control's "programming calls" are made
from the reference's own tables, in the plain form compare.py reads. The
benchmark's own runs never run it.
"""

from __future__ import annotations

import argparse
import collections
import json
import sys
from typing import Dict, List, Tuple

from chipbench import compare
from chipbench.reference import Table

BREAKAGES = ("stale", "first_hop")


# a programmed route in the shape compare.routes_as_table reads
_Route = collections.namedtuple("_Route", "dest nexthops")
_NextHop = collections.namedtuple("_NextHop", "address iface metric")


def _break_table(table: Table, breakage: str) -> Table:
    if breakage != "first_hop":
        return table
    return {p: frozenset(sorted(nhs)[:1]) for p, nhs in table.items()}


def _calls(before: Table, after: Table) -> List[Tuple[str, list]]:
    """The programming calls that move an agent from `before` to `after`."""
    adds = [
        _Route(p, [_NextHop(*nh) for nh in nhs])
        for p, nhs in after.items()
        if before.get(p) != nhs
    ]
    deletes = [p for p in before if p not in after]
    calls = []
    if deletes:
        calls.append(("delete_unicast_routes", deletes))
    if adds:
        calls.append(("add_unicast_routes", adds))
    return calls


def control_run(cell: dict, seed: int, n_events: int, breakage: str):
    """(correct, numbers beside limits) of a window of `n_events` events
    answered by the reference with `breakage` applied."""
    config, params = cell["config_data"], cell["params"]
    n_warm = int(params["warmup_events"])
    verify = compare.choose_events(n_events, int(params["verify_events"]), seed)
    tables = compare.replay_reference(
        config, params, seed, n_warm, n_events, list(range(n_events))
    )
    lag = 1 if breakage == "stale" else 0

    def answered(i: int) -> Table:  # what the control holds after i events
        return _break_table(tables[max(i - lag, 0)], breakage)

    agent_events = [
        _calls(answered(i), answered(i + 1)) for i in range(n_events)
    ]
    correct, compared, notes = compare.compare(
        final_table=answered(n_events),
        agent_events=agent_events,
        tables=tables.__getitem__,
        verify=verify,
        updates_per_event=[1] * n_events,
        counter_moves={},
    )
    return correct, compared, notes


def main(argv=None) -> int:
    from chipbench.run import resolve_cell

    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--events", type=int, default=400)
    args = parser.parse_args(argv)
    cell = resolve_cell(args.workload)
    out: Dict[str, dict] = {}
    for breakage in BREAKAGES:
        correct, compared, _ = control_run(cell, args.seed, args.events, breakage)
        out[breakage] = {
            "correct": correct,
            **{k: v["value"] for k, v in compared.items()},
        }
    print(json.dumps({"workload": args.workload, "seed": args.seed,
                      "events": args.events, "control": out}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

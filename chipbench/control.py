"""The control of the comparison: the reference put in the program's
place with one stated guarantee broken. It has to come out not correct.

The system states no precision, so the control breaks a guarantee of the
configuration ("every route the agent holds equals the reference's on the
same LSDB"), in the ways that would tempt a later change:

  stale       each event is answered from the LSDB as it was one event
              earlier (an acknowledgement before the solve has the event):
              a stale answer where a current one is promised
  first_hop   every next-hop set, of a route or a label route, is cut to
              one member (the equal-cost multipath extraction left out): an
              approximate answer where an exact one is promised
  push_stack  every push stack loses its bottom label (the destination's
              own, or its prepend label): a label stack built one node
              short. Only where the configuration's prefixes push labels
              (`prefix_forwarding` SR_MPLS); elsewhere every stack is empty

    python3 -m chipbench.control --workload <cell> --seed <n> --events <n>

needs no chip and no daemon: the control's "programming calls" are made
from the reference's own tables, in the plain form compare.py reads. The
benchmark's own runs never run it.
"""

from __future__ import annotations

import argparse
import collections
import enum
import json
import sys
from typing import Dict, List, Tuple

from chipbench import compare
from chipbench.reference import Tables

BREAKAGES = ("stale", "first_hop")  # every configuration's
LABEL_BREAKAGES = ("push_stack",)  # a configuration's whose prefixes push labels


def breakages(config: dict) -> Tuple[str, ...]:
    """The breakages that can show on the configuration."""
    pushes = config.get("prefix_forwarding", {}).get("type") == "SR_MPLS"
    return BREAKAGES + (LABEL_BREAKAGES if pushes else ())


# programmed routes in the shapes compare.py reads
_Route = collections.namedtuple("_Route", "dest nexthops")
_MplsRoute = collections.namedtuple("_MplsRoute", "top_label nexthops")
_NextHop = collections.namedtuple("_NextHop", "address iface metric mpls_action")
_Action = collections.namedtuple("_Action", "action push_labels swap_label")
_Code = enum.Enum("_Code", "PUSH SWAP PHP POP_AND_LOOKUP")


def _unicast_hop(address, iface, metric, push) -> _NextHop:
    action = _Action(_Code.PUSH, push, None) if push else None
    return _NextHop(address, iface, metric, action)


def _label_hop(address, iface, action, labels) -> _NextHop:
    swap = labels[0] if action == "SWAP" else None
    push = labels if action == "PUSH" else ()
    return _NextHop(address, iface, 0, _Action(_Code[action], push, swap))


def _break_tables(tables: Tables, breakage: str) -> Tables:
    if breakage == "first_hop":
        return tuple(
            {key: frozenset(sorted(nhs)[:1]) for key, nhs in table.items()}
            for table in tables
        )
    if breakage == "push_stack":
        unicast, mpls = tables
        return {
            p: frozenset((*nh[:3], nh[3][1:]) for nh in nhs)
            for p, nhs in unicast.items()
        }, mpls
    return tables


def _calls(before: Tables, after: Tables) -> List[Tuple[str, list]]:
    """The programming calls that move an agent from `before` to `after`,
    in Fib's order."""
    calls = []
    for side, (route, hop) in enumerate(((_Route, _unicast_hop), (_MplsRoute, _label_hop))):
        was, now = before[side], after[side]
        deletes = [key for key in was if key not in now]
        adds = [
            route(key, [hop(*nh) for nh in nhs])
            for key, nhs in now.items()
            if was.get(key) != nhs
        ]
        kind = "mpls" if side else "unicast"
        if deletes:
            calls.append((f"delete_{kind}_routes", deletes))
        if adds:
            calls.append((f"add_{kind}_routes", adds))
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

    def answered(i: int) -> Tables:  # what the control holds after i events
        return _break_tables(tables[max(i - lag, 0)], breakage)

    agent_events = [
        _calls(answered(i), answered(i + 1)) for i in range(n_events)
    ]
    correct, compared, notes = compare.compare(
        final=answered(n_events),
        agent_events=agent_events,
        tables=tables.__getitem__,
        verify=verify,
        updates_per_event=[1] * n_events,
        counter_moves={},
        segment_routing=compare.segment_routing(config),
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
    for breakage in breakages(cell["config_data"]):
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

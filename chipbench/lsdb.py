"""The link-state database of a run, as plain data.

`Lsdb` is what the reference reads: node names, a metric per directed
adjacency, which links are up, one prefix per node and whether the node
announces it, one MPLS node label per node. It imports nothing of the
program. The
encoding that the daemon is fed (the program's own AdjacencyDatabase /
PrefixDatabase types, serialized as KvStore values) is `WireEncoder`, kept
apart so that the reference never sees a program object.

Interface names and next-hop addresses follow the scheme of the program's
test fixtures (`if-<local>-<remote>`, crc32-derived link-local addresses);
they are inputs, generated here, and the comparison reads them back from
the agent's table.
"""

from __future__ import annotations

import base64
import zlib
from typing import Dict, List, Optional, Set, Tuple

from chipbench.topologies import Edge

AREA = "0"
# a configuration's `prefix_forwarding` where it states none
PREFIX_FORWARDING = {"type": "IP", "algorithm": "SP_ECMP"}


def _crc(text: str) -> int:
    return zlib.crc32(text.encode())


def if_name(local: str, remote: str) -> str:
    return f"if-{local}-{remote}"


def nexthop_v4(local: str, remote: str) -> str:
    """Address of `remote` as `local`'s adjacency advertises it."""
    return f"169.254.{_crc(remote) % 255}.{_crc(if_name(remote, local)) % 255}"


def nexthop_v6(remote: str) -> str:
    return f"fe80::{_crc(remote) % 0xFFFF:x}"


class Lsdb:
    """Nodes, directed metrics, link state and prefixes; mutated by traffic
    events. Every link is up and every /24 announced until one says
    otherwise."""

    def __init__(self, edges: List[Edge]) -> None:
        self.metric: Dict[str, Dict[str, int]] = {}
        for a, b, metric in edges:
            if b in self.metric.setdefault(a, {}):
                raise ValueError(f"parallel link {a}<->{b}: not supported")
            self.metric[a][b] = metric
            self.metric.setdefault(b, {})[a] = metric
        self.nodes: List[str] = sorted(self.metric)
        self.n_links = len(edges)
        self.prefix_of: Dict[str, str] = {
            node: f"10.{i // 256}.{i % 256}.0/24"
            for i, node in enumerate(self.nodes)
        }
        # every node's MPLS node label, in its adjacency database
        self.label_of: Dict[str, int] = {
            node: i + 100 for i, node in enumerate(self.nodes)
        }
        self.down: Set[Tuple[str, str]] = set()  # directed: a down link both ways
        self.withdrawn: Set[str] = set()  # nodes that do not announce their /24

    def up_peers(self, node: str) -> Dict[str, int]:
        """`node`'s adjacencies that are up: peer -> metric."""
        peers = self.metric[node]
        if not self.down:
            return peers
        return {p: m for p, m in peers.items() if (node, p) not in self.down}

    def set_metric(self, a: str, b: str, metric: int) -> Tuple[str, str]:
        """Both directions of link a<->b; returns the nodes whose
        adjacency database changed."""
        if b not in self.metric.get(a, {}):
            raise KeyError(f"no link {a}<->{b}")
        self.metric[a][b] = metric
        self.metric[b][a] = metric
        return (a, b)

    def set_link_up(self, a: str, b: str, up: bool) -> Tuple[str, ...]:
        """Both directions of link a<->b, which keeps its metric; returns
        the nodes whose adjacency database changed."""
        if b not in self.metric.get(a, {}):
            raise KeyError(f"no link {a}<->{b}")
        if up == ((a, b) not in self.down):
            return ()
        if up:
            self.down -= {(a, b), (b, a)}
        else:
            self.down |= {(a, b), (b, a)}
        return (a, b)

    def set_announced(self, node: str, announced: bool) -> Tuple[str, ...]:
        """Whether `node` announces its /24 (`prefix_of` is the plan and
        does not change); returns the nodes whose prefix database changed."""
        if node not in self.metric:
            raise KeyError(f"no node {node}")
        if announced == (node not in self.withdrawn):
            return ()
        if announced:
            self.withdrawn.discard(node)
        else:
            self.withdrawn.add(node)
        return (node,)


class WireEncoder:
    """Lsdb -> the daemon's KvStore keys, as ctrl `setKvStoreKeyVals` JSON.

    The one place of the benchmark that builds the program's types. The
    serialized bytes of a node's adjacency database are cached by its up
    adjacencies and their metrics, so an event's payload costs a dict
    lookup and a version bump. A down link is in neither end's database
    (upstream's LinkMonitor withdraws the adjacency and marks nothing); a
    node that does not announce its /24 sends a prefix database with no
    entry, which Decision reads as the withdrawal.
    """

    def __init__(self, lsdb: Lsdb, prefix_forwarding: Optional[dict] = None) -> None:
        self.lsdb = lsdb
        # what every PrefixEntry states: a configuration's
        # `prefix_forwarding`, upstream's default IP / SP_ECMP without one
        self.forwarding = dict(PREFIX_FORWARDING, **(prefix_forwarding or {}))
        self.versions: Dict[str, int] = {}
        self._adj_bytes: Dict[tuple, str] = {}

    def _adj_value(self, node: str) -> str:
        # the program's types are imported here and not at the module's
        # top, so that the reference, which imports Lsdb, never loads them
        from openr_tpu.types import Adjacency, AdjacencyDatabase
        from openr_tpu.utils import serializer

        peers = self.lsdb.up_peers(node)
        key = (node, tuple(peers.items()))
        cached = self._adj_bytes.get(key)
        if cached is None:
            db = AdjacencyDatabase(
                this_node_name=node,
                adjacencies=[
                    Adjacency(
                        other_node_name=peer,
                        if_name=if_name(node, peer),
                        other_if_name=if_name(peer, node),
                        metric=metric,
                        nexthop_v6=nexthop_v6(peer),
                        nexthop_v4=nexthop_v4(node, peer),
                    )
                    for peer, metric in peers.items()
                ],
                area=AREA,
                node_label=self.lsdb.label_of[node],
            )
            cached = self._adj_bytes[key] = base64.b64encode(serializer.dumps(db)).decode()
        return cached

    def _prefix_value(self, node: str) -> str:
        from openr_tpu.types import (
            IpPrefix,
            PrefixDatabase,
            PrefixEntry,
            PrefixForwardingAlgorithm,
            PrefixForwardingType,
        )
        from openr_tpu.utils import serializer

        entries = (
            [] if node in self.lsdb.withdrawn
            else [PrefixEntry(
                IpPrefix(self.lsdb.prefix_of[node]),
                forwarding_type=PrefixForwardingType[self.forwarding["type"]],
                forwarding_algorithm=PrefixForwardingAlgorithm[
                    self.forwarding["algorithm"]
                ],
            )]
        )
        db = PrefixDatabase(node, entries, area=AREA)
        return base64.b64encode(serializer.dumps(db)).decode()

    def key_vals(self, keys: List[str]) -> Dict[str, dict]:
        out = {}
        for key in keys:
            kind, node = key.split(":", 1)
            value = (
                self._adj_value(node) if kind == "adj"
                else self._prefix_value(node)
            )
            self.versions[key] = self.versions.get(key, 0) + 1
            out[key] = {
                "version": self.versions[key],
                "originator_id": node,
                "value": value,
            }
        return out

    def all_keys(self) -> List[str]:
        return [f"adj:{n}" for n in self.lsdb.nodes] + [
            f"prefix:{n}" for n in self.lsdb.nodes
        ]

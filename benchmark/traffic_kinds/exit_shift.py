"""One of a router's exits from its area shifts per event, and the next
event gives it back: what a dual-homed access router of a multi-area WAN
sees when the measured RTT of one of its two uplinks moves, or a border
router of its region is drained for maintenance. Every route to a prefix of
another area is advertised by the area's border routers alike, so ONE such
change moves all of them at once: the next-hop set, the alternate, or the
metric of every inter-area route.

`link_flap` and `spt_link` cannot say this: the first knows no metrics, the
second never takes a link of the vantage and counts the routes behind a
link by one advertiser a prefix. So this kind looks at the LSDB at rest
(lsdbs/region.py's model: `adj_dbs`, `prefix_dbs` of the vantage's area)
with its own Dijkstra and its own selection among advertisers. The
inter-area prefixes are those whose every entry carries an `area_stack`;
the border routers are their advertisers. Prefixes with the same set of
(advertiser, preferences, advertised distance) share a route; the TABLE of
the inter-area routes is, per such set, (metric, next hops, alternate).
Candidates:

  (a) the links of the area, the vantage's own among them, whose RTT step
      at the least factor of `factor_range` changes that table. Only a link
      that lies on a shortest path from the vantage or one of its
      neighbours to a border router, or from a neighbour to the vantage,
      can: the others are not tried;
  (b) the border routers whose drain (overload bit: no transit) changes it.

`candidates` says how many the deployment's mix takes, {"links": 2,
"border_routers": 1}: the vantage's own links first, then by name. Fewer
found is an error. With two links A, B (in that order) and one border
router D:

One cycle is six events, all timed, one thing held at a time: each of the
three in turn is changed and then given back, so every event moves the
table between the one at rest and one with an exit shifted. A link takes an
RTT step, its metric m to max(ceil(m * f), m + 1) both ways with f drawn
from `factor_range`, and gets the generator's metric back ("metric" of
lsdb.py); a border router is drained by its overload bit and given back
("drain" / "undrain" of lsdbs/node_drain.py: one adj: key, every adjacency
as it stood). No link goes down or up. The seed draws every f, and from
the fifth cycle on the order of the three inside each cycle. The first four
cycles are the warm-up's (harness.py: a rotation of six events at the
period, then `warmup_bursts`, at least twice) and run in a fixed order, A B
D, A B D, B A D, B A D: with `warmup_bursts` [1, 2, 2, 1] the second and
the fourth are sent as change | give back + change | give back + change |
give back, so that each pair of the three has met in one solve epoch (A+B,
B+D; B+A, A+D) before the window: what a held loop can make of two events
that fall due together (the delta scatter compiles for each count of
changed weights). The classes and the count of events are the same
whatever the seed.
"""

from __future__ import annotations

import math
import random

import numpy as np
from scipy.sparse import csr_matrix
from scipy.sparse.csgraph import dijkstra

WARMUP_ORDERS = ((0, 1, 2), (0, 1, 2), (1, 0, 2), (1, 0, 2))


def rotation_events(params: dict) -> int:
    """Events in one cycle: every candidate changed and given back."""
    taken = params["candidates"]
    return 2 * (taken["links"] + taken["border_routers"])


def stepped(metric: int, factor: float) -> int:
    return max(math.ceil(metric * factor), metric + 1)


class Area:
    """The vantage's area at rest, and the table of its inter-area routes
    under a trial change."""

    def __init__(self, lsdb, vantage: str):
        self.names = [db.this_node_name for db in lsdb.adj_dbs]
        self.index = {name: i for i, name in enumerate(self.names)}
        self.root = self.index[vantage]
        self.metric: dict[tuple, int] = {}
        for db in lsdb.adj_dbs:
            if db.is_overloaded:
                raise ValueError("exit_shift: a router is drained at rest")
            for adj in db.adjacencies:
                self.metric[self.index[db.this_node_name],
                            self.index[adj.other_node_name]] = adj.metric
        # the vantage's links in a fixed order: (neighbour, metric)
        self.uplinks = sorted(
            v for (u, v) in self.metric if u == self.root
        )
        # the sets of advertisers that inter-area prefixes share
        by_prefix: dict[str, list] = {}
        for db in lsdb.prefix_dbs:
            for entry in db.prefix_entries:
                m = entry.metrics
                by_prefix.setdefault(entry.prefix, []).append((
                    self.index[db.this_node_name], m.path_preference,
                    m.source_preference, m.distance, bool(entry.area_stack),
                ))
        self.groups = sorted({
            tuple(sorted(e[:4] for e in entries))
            for entries in by_prefix.values() if all(e[4] for e in entries)
        })
        if not self.groups:
            raise ValueError("exit_shift: no prefix of another area")
        self.borders = sorted({e[0] for g in self.groups for e in g})

    def distances(self, metric: dict, drained: frozenset, transpose=False):
        """Rows: from the vantage, then from each of its neighbours (or,
        transposed, TO the vantage and to each border router)."""
        edges = [
            (u, v, w) for (u, v), w in metric.items() if u not in drained
        ]
        src, dst, w = (np.asarray(x) for x in zip(*edges))
        if transpose:
            src, dst = dst, src
        n = len(self.names)
        sources = (
            [self.root] + self.borders if transpose
            else [self.root] + self.uplinks
        )
        return dijkstra(
            csr_matrix((w.astype(np.float64), (src, dst)), shape=(n, n)),
            directed=True, indices=sources,
        )

    def table(self, metric: dict, drained: frozenset = frozenset()) -> tuple:
        """Per group of advertisers: (metric, next hops, alternate)."""
        dist = self.distances(metric, drained)
        me, nbr = dist[0], dist[1:]
        out = []
        for group in self.groups:
            left = [e for e in group if np.isfinite(me[e[0]])]
            for key, best in ((1, max), (2, max), (3, min)):
                if left:
                    want = best(e[key] for e in left)
                    left = [e for e in left if e[key] == want]
            left = [e[0] for e in left]
            usable = [a for a in left if a not in drained] or left
            if not usable:
                out.append(None)
                continue
            cost = min(me[a] for a in usable)
            nearest = [a for a in usable if me[a] == cost]
            hops, alt = [], None
            for k, n in enumerate(self.uplinks):
                w = metric[self.root, n]
                if any(w + nbr[k][a] == cost for a in nearest):
                    hops.append(n)
                    continue
                d = min(nbr[k][a] for a in usable)
                if d < nbr[k][self.root] + cost and (
                    alt is None or w + d < alt[0]
                ):
                    alt = (w + d, n)
            out.append((cost, tuple(hops), alt))
        return tuple(out)

    def on_a_path(self) -> list[tuple[int, int]]:
        """The links (u < v) that lie on a shortest path from the vantage
        or a neighbour of it to a border router, or from a neighbour to the
        vantage: the only ones whose worsening can move the table."""
        none = frozenset()
        fwd = self.distances(self.metric, none)
        rev = self.distances(self.metric, none, transpose=True)
        targets = [self.root] + self.borders
        found = set()
        for (u, v), w in self.metric.items():
            for s in range(fwd.shape[0]):
                for t, node in enumerate(targets):
                    if fwd[s][u] + w + rev[t][v] == fwd[s][node]:
                        found.add((min(u, v), max(u, v)))
        return sorted(found)


def find_candidates(lsdb, params: dict) -> dict:
    """-> {"area", "rest", "links": [(u, v)], "border_routers": [node]},
    every one found by the rule, the vantage's own links first."""
    area = Area(lsdb, params["vantage"])
    rest = area.table(area.metric)
    least = params["factor_range"][0]
    links = []
    for u, v in area.on_a_path():
        m = area.metric[u, v]
        if area.metric[v, u] != m:
            raise ValueError("exit_shift: a link's metric differs by "
                             "direction")
        trial = {**area.metric, (u, v): stepped(m, least),
                 (v, u): stepped(m, least)}
        if area.table(trial) != rest:
            links.append((u, v))
    links.sort(key=lambda l: (area.root not in l, area.names[l[0]],
                              area.names[l[1]]))
    routers = [
        b for b in area.borders
        if area.table(area.metric, frozenset({b})) != rest
    ]
    return {"area": area, "rest": rest, "links": links,
            "border_routers": routers}


def plan(lsdb, params: dict, seed: int):
    """Yields events without end: {"ops": [...], "class": "step" | "unstep"
    | "drain" | "undrain", "stratum": the link or the router}."""
    taken = params["candidates"]
    if (taken["links"], taken["border_routers"]) != (2, 1):
        raise ValueError("exit_shift: a cycle is two links and one border "
                         "router")
    found = find_candidates(lsdb, params)
    area = found["area"]
    for key in taken:
        if len(found[key]) < taken[key]:
            raise ValueError(
                f"exit_shift: the rule finds {len(found[key])} of {key}, "
                f"the mix takes {taken[key]}"
            )
    name = area.names
    candidates = [
        ("link", name[u], name[v], area.metric[u, v])
        for u, v in found["links"][:2]
    ] + [("router", name[found["border_routers"][0]])]
    lo_f, hi_f = params["factor_range"]
    rng = random.Random(f"{seed}/exit-shift")
    cycle = 0
    while True:
        order = list(range(3))
        if cycle < len(WARMUP_ORDERS):
            order = list(WARMUP_ORDERS[cycle])
        else:
            rng.shuffle(order)
        cycle += 1
        for k in order:
            if candidates[k][0] == "link":
                _, a, b, m = candidates[k]
                step = stepped(m, rng.uniform(lo_f, hi_f))
                yield {"ops": [("metric", a, b, step)], "class": "step",
                       "stratum": f"{a}--{b}"}
                yield {"ops": [("metric", a, b, m)], "class": "unstep",
                       "stratum": f"{a}--{b}"}
            else:
                node = candidates[k][1]
                yield {"ops": [("drain", node)], "class": "drain",
                       "stratum": node}
                yield {"ops": [("undrain", node)], "class": "undrain",
                       "stratum": node}

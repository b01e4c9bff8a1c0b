"""One link on the vantage's shortest-path tree takes a step in its metric
per event (a measured RTT that rose: the optical path under the link was
rerouted), and a later event of the same stratum gives the generator's
own metric back.

`link_flap` cannot say this where every link has a metric of its own and
the graph is irregular: two links in three lie off the vantage's tree, a
change to one of those moves no route, and an event that moves no route is
never acked. So this kind looks at the graph. By its own Dijkstra from
`vantage` over the generator's databases it finds the shortest-path DAG
and keeps as candidates the links (u, v) where u is v's only DAG parent:
every shortest path to v ends in that link, so when it worsens dist(v)
rises and v's own route changes, and when it is given back the route
returns. desc(v) counts the nodes reachable from v in the DAG, v among
them: only their routes can move.

Parameters (traffic/<name>.json, overlaid by traffic/<name>.<config>.json):

  vantage       the node whose tree it is (the configuration's vantage);
                its own links are never candidates. A link's metric is
                taken to be the same both ways (an RTT is)
  group         a regular expression with one capture that names a node's
                region; regions are ranked by the distance of their
                nearest node and cut into a "near", a "mid" and a "far"
                third (the vantage's own region is the nearest)
  factor_range  a change sets the metric m to max(ceil(m * f), m + 1),
                both directions, f drawn from this range
  strata        a list of strata, or a list of levels, each a list of
                strata, as `link_flap`: one cycle changes one link in
                every stratum, level by level, and then restores them, the
                levels in reverse. A stratum is {"name", "desc": [lo, hi],
                "third": "far" | "mid" | "near"}: the candidates whose v
                has lo <= desc(v) <= hi and lies in a region of that
                third. Fewer than `min_candidates` (8) is an error.

A draw is rejected while its v lies in desc of a held link's v or the
other way round: held links never nest, so every change and every restore
moves a route whatever else is held, in the same solve epoch or not. The
strata, the classes and the count of events are the same whatever the
seed; the seed draws the link and the factor.
"""

from __future__ import annotations

import math
import random
import re

import numpy as np
from scipy.sparse import csr_matrix
from scipy.sparse.csgraph import dijkstra

THIRDS = ("near", "mid", "far")
MIN_CANDIDATES = 8
DRAWS = 64  # rejected draws before the stratum is searched in order


def _levels(params: dict) -> list[list[dict]]:
    strata = params["strata"]
    return strata if isinstance(strata[0], list) else [strata]


def rotation_events(params: dict) -> int:
    """Events in one cycle: every stratum changed and restored."""
    return 2 * sum(len(level) for level in _levels(params))


class Tree:
    """The vantage's shortest-path DAG on the LSDB as it stands."""

    def __init__(self, lsdb, vantage: str):
        self.names = [db.this_node_name for db in lsdb.adj_dbs]
        self.index = {name: i for i, name in enumerate(self.names)}
        src, dst, w = [], [], []
        for db in lsdb.adj_dbs:
            me = self.index[db.this_node_name]
            for adj in db.adjacencies:
                src.append(me)
                dst.append(self.index[adj.other_node_name])
                w.append(adj.metric)
        src, dst = np.asarray(src), np.asarray(dst)
        w = np.asarray(w, np.float64)
        n = len(self.names)
        self.root = self.index[vantage]
        self.dist = dijkstra(
            csr_matrix((w, (src, dst)), shape=(n, n)), directed=True,
            indices=self.root,
        )
        tight = self.dist[src] + w == self.dist[dst]
        self.parents = np.bincount(dst[tight], minlength=n)
        self.children: dict[int, list[int]] = {}
        for u, v in zip(src[tight].tolist(), dst[tight].tolist()):
            self.children.setdefault(u, []).append(v)
        self.metric = {
            (u, v): int(m)
            for u, v, m in zip(src.tolist(), dst.tolist(), w.tolist())
        }

    def reach(self, v: int, limit: int) -> set[int]:
        """v and what the DAG reaches from it; stops once over `limit`."""
        seen, todo = {v}, [v]
        while todo and len(seen) <= limit:
            for c in self.children.get(todo.pop(), ()):
                if c not in seen:
                    seen.add(c)
                    todo.append(c)
        return seen

    def candidates(self, limit: int) -> list[tuple[int, int, int]]:
        """(u, v, desc(v)) for every link whose u is v's only parent and
        is not the vantage; desc is limit + 1 where it is more."""
        return [
            (u, v, len(self.reach(v, limit)))
            for u, below in sorted(self.children.items()) for v in below
            if self.parents[v] == 1 and u != self.root
        ]


def strata_candidates(lsdb, params: dict):
    """-> (tree, one list of (u, v, desc) per stratum, in the plan's
    order of strata)."""
    tree = Tree(lsdb, params["vantage"])
    specs = [spec for level in _levels(params) for spec in level]
    group = re.compile(params["group"])
    region = [group.search(name).group(1) for name in tree.names]
    nearest: dict[str, float] = {}
    for r, d in zip(region, tree.dist.tolist()):
        nearest[r] = min(nearest.get(r, math.inf), d)
    ranked = sorted(nearest, key=lambda r: (nearest[r], r))
    third = {r: THIRDS[i * 3 // len(ranked)] for i, r in enumerate(ranked)}
    found = tree.candidates(max(spec["desc"][1] for spec in specs))
    out = []
    for spec in specs:
        lo, hi = spec["desc"]
        picked = [
            c for c in found
            if lo <= c[2] <= hi and third[region[c[1]]] == spec["third"]
        ]
        if len(picked) < params.get("min_candidates", MIN_CANDIDATES):
            raise ValueError(
                f"spt_link: stratum {spec['name']} has {len(picked)} "
                f"candidate links: widen its band of desc"
            )
        out.append(picked)
    return tree, out


def plan(lsdb, params: dict, seed: int):
    """Yields events without end: {"ops": [("metric", a, b, m)], "class":
    "change" | "restore", "stratum": str}."""
    levels = _levels(params)
    specs = [spec for level in levels for spec in level]
    if len(specs) < max(params.get("warmup_bursts", []), default=2):
        raise ValueError("spt_link: fewer strata than the longest burst")
    tree, cands = strata_candidates(lsdb, params)
    lo_f, hi_f = params["factor_range"]
    limit = max(spec["desc"][1] for spec in specs)
    rngs = [random.Random(f"{seed}/{k}") for k in range(len(specs))]
    # the strata's numbers: forwards to change, and with the levels
    # reversed to restore
    forwards = range(len(specs))
    number = iter(forwards)
    numbered = [[next(number) for _ in level] for level in levels]
    back = [k for level in reversed(numbered) for k in level]
    held: list = [None] * len(specs)  # (u, v, what v reaches)

    def free(v: int, below: set) -> bool:
        return not any(
            h is not None and (v in h[2] or h[1] in below) for h in held
        )

    def draws(k: int):
        for _ in range(DRAWS):
            yield rngs[k].choice(cands[k])
        start = rngs[k].randrange(len(cands[k]))
        yield from cands[k][start:] + cands[k][:start]

    def draw(k: int):
        for u, v, _ in draws(k):
            below = tree.reach(v, limit)
            if free(v, below):
                if tree.metric[u, v] != tree.metric[v, u]:
                    raise ValueError("spt_link: a link's metric differs "
                                     "by direction")
                return u, v, below
        raise ValueError(
            f"spt_link: every link of stratum {specs[k]['name']} nests "
            f"with a held one"
        )

    while True:
        for k in forwards:
            u, v, below = held[k] = draw(k)
            m = tree.metric[u, v]
            stepped = max(math.ceil(m * rngs[k].uniform(lo_f, hi_f)), m + 1)
            yield {
                "ops": [("metric", tree.names[u], tree.names[v], stepped)],
                "class": "change", "stratum": specs[k]["name"],
            }
        for k in back:
            u, v, _ = held[k]
            held[k] = None
            yield {
                "ops": [("metric", tree.names[u], tree.names[v],
                         tree.metric[u, v])],
                "class": "restore", "stratum": specs[k]["name"],
            }

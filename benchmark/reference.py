"""The plain reference: the routes a vantage node must hold for an LSDB,
from Dijkstra alone, and the comparison that decides `correct`.

It imports nothing of the program's Decision code. It reads the
adjacency and prefix databases the benchmark's own LSDB copy holds
(lsdb.py) and gives, for every prefix another node advertises,

    prefix -> (metric, {(neighbour, interface, metric)},
               {(neighbour, interface, alternate metric)})

- a link exists where both ends list each other with matching interface
  names, and costs in each direction what that end advertises;
- the metric is the shortest distance from the vantage to the advertiser;
- the next hops are the vantage's links (v, N) with
  w(v, N) + dist_N(dst) == dist_v(dst): the first hops of the
  shortest-path DAG (ECMP);
- with LFA on, the one backup is the cheapest link, not a primary, whose
  neighbour N satisfies RFC 5286's loop-free inequality
  dist_N(dst) < dist_N(v) + dist_v(dst); a neighbour that is the
  destination qualifies at distance 0; ties go to the link that sorts
  first by (lower end, its interface, upper end, its interface).

What the deployments of this benchmark do not use is refused, not
guessed: drained nodes or adjacencies, a prefix with two advertisers,
more than one area.
"""

from __future__ import annotations

import numpy as np
from scipy.sparse import csr_matrix
from scipy.sparse.csgraph import dijkstra


class Unsupported(Exception):
    pass


def _graph(adj_dbs: list):
    index = {db.this_node_name: i for i, db in enumerate(adj_dbs)}
    advertised = {}
    for db in adj_dbs:
        if db.is_overloaded:
            raise Unsupported(f"{db.this_node_name} is drained")
        for adj in db.adjacencies:
            if adj.is_overloaded or adj.adj_only_used_by_other_node:
                raise Unsupported(
                    f"{db.this_node_name}: held or drained adjacency"
                )
            key = (db.this_node_name, adj.if_name,
                   adj.other_node_name, adj.other_if_name)
            advertised[key] = adj.metric
    rows, cols, weights = [], [], []
    for (me, my_if, other, other_if), metric in advertised.items():
        if (other, other_if, me, my_if) in advertised and other in index:
            if metric <= 0:
                raise Unsupported(f"metric {metric} on {me} -> {other}")
            rows.append(index[me])
            cols.append(index[other])
            weights.append(metric)
    n = len(index)
    # parallel links between one pair would be summed by csr_matrix
    if len(set(zip(rows, cols))) != len(rows):
        raise Unsupported("parallel links between one pair of nodes")
    graph = csr_matrix(
        (np.asarray(weights, np.float64), (rows, cols)), shape=(n, n)
    )
    return index, advertised, graph


def routes(adj_dbs: list, prefix_dbs: list, me: str, lfa: bool) -> dict:
    index, advertised, graph = _graph(adj_dbs)
    if me not in index:
        raise Unsupported(f"vantage {me} is not in the LSDB")
    # the vantage's verified links, in the order LFA breaks ties by
    links = []
    for (node, my_if, other, other_if), metric in advertised.items():
        if node == me and (other, other_if, me, my_if) in advertised:
            ends = sorted(((me, my_if), (other, other_if)))
            links.append((ends, other, my_if, metric))
    links.sort()
    sources = [index[me]] + [index[other] for _, other, _, _ in links]
    dist = dijkstra(graph, directed=True, indices=sources)
    dist_me, dist_nbr = dist[0], dist[1:]

    owner: dict[str, int] = {}
    for db in prefix_dbs:
        if db.area != adj_dbs[0].area:
            raise Unsupported("more than one area")
        for entry in db.prefix_entries:
            if entry.prefix in owner:
                raise Unsupported(f"{entry.prefix} has two advertisers")
            owner[entry.prefix] = index.get(db.this_node_name, -1)

    # per destination node: which links are primaries, which is the backup
    n = len(index)
    reach = np.isfinite(dist_me)
    primary = np.zeros((len(links), n), bool)
    alt = np.full((len(links), n), np.inf)
    for k, (_, other, _, metric) in enumerate(links):
        primary[k] = reach & (metric + dist_nbr[k] == dist_me)
        if lfa:
            loop_free = dist_nbr[k] < dist_nbr[k][index[me]] + dist_me
            loop_free[index[other]] = True
            alt[k] = np.where(
                loop_free & ~primary[k], metric + dist_nbr[k], np.inf
            )
    # argmin takes the first of equal minima: the link that sorts first
    backup = np.argmin(alt, axis=0) if lfa and links else None

    out = {}
    for prefix, node in owner.items():
        if node < 0 or node == index[me] or not reach[node]:
            continue
        cost = int(dist_me[node])
        hops = frozenset(
            (other, my_if, cost)
            for k, (_, other, my_if, _) in enumerate(links)
            if primary[k, node]
        )
        backups = frozenset()
        if backup is not None and np.isfinite(alt[backup[node], node]):
            _, other, my_if, _ = links[backup[node]]
            backups = frozenset(
                {(other, my_if, int(alt[backup[node], node]))}
            )
        out[prefix] = (cost, hops, backups)
    return out


def programmed(unicast: dict) -> dict:
    """What Fib's service holds, in the reference's form."""
    return {
        prefix: (
            entry.igp_cost,
            frozenset(
                (nh.neighbor_node_name, nh.if_name, nh.metric)
                for nh in entry.nexthops
            ),
            frozenset(
                (nh.neighbor_node_name, nh.if_name, nh.metric)
                for nh in entry.lfa_nexthops
            ),
        )
        for prefix, entry in unicast.items()
    }


def compare(got: dict, want: dict) -> dict:
    """Route for route, exactly. -> counts and the first few differences."""
    missing = sorted(want.keys() - got.keys())
    extra = sorted(got.keys() - want.keys())
    differing = sorted(
        p for p in want.keys() & got.keys() if got[p] != want[p]
    )
    examples = [
        {"prefix": p, "got": _plain(got.get(p)), "want": _plain(want.get(p))}
        for p in (missing + extra + differing)[:3]
    ]
    return {
        "routes_compared": len(want),
        "missing": len(missing),
        "extra": len(extra),
        "differing": len(differing),
        "examples": examples,
    }


def _plain(route):
    if route is None:
        return None
    cost, hops, backups = route
    return [cost, sorted(hops), sorted(backups)]

"""The plain reference of a deployment whose switches are drained by their
overload bit (lsdbs/node_drain.py): the routes a vantage must hold where
some nodes carry no transit traffic. Independent of the program's Decision
code, from Dijkstra alone; the form of a route, the comparison and what a
link is are reference.py's:

    prefix -> (metric, {(neighbour, interface, metric)},
               {(neighbour, interface, alternate metric)})

The rule (upstream LinkState::runSpf, "overloaded-node transit drain", and
SpfSolver.cpp:709-731):

- a drained node (`AdjacencyDatabase.is_overloaded`) keeps every link, and
  a shortest path may END at it but never pass THROUGH it. So distances are
  Dijkstra's on the graph WITHOUT THE OUT-EDGES OF EVERY DRAINED NODE: its
  in-edges stay, and whoever reaches it stops there;
- the vantage is never drained in this deployment (an overloaded vantage is
  refused: upstream exempts the root of an SPF from its own bit, which this
  graph does not), so that one graph serves every source, the vantage and
  each of its neighbours: a drained neighbour N reaches only itself, which
  makes it a next hop to N's own prefixes and to no other, and no loop-free
  alternate but to itself;
- next hops and the RFC 5286 alternate follow from those distance fields
  exactly as in reference.py: the vantage's links (v, N) with
  w(v, N) + dist_N(dst) == dist_v(dst); the cheapest non-primary link
  whose neighbour satisfies dist_N(dst) < dist_N(v) + dist_v(dst), a
  neighbour that is the destination qualifying at distance 0;
- per prefix the drained advertisers are dropped unless all are drained
  (`announcers`): a prefix whose only advertiser is drained keeps its
  route, at the distance TO the drained node.

Refused, not guessed (`reference.Unsupported`): a drained adjacency
(`Adjacency.is_overloaded`), a held one, a soft drain (a non-zero
`node_metric_increment`), a second advertiser of a prefix, a second area,
parallel links, a non-positive metric.

`routes` returns a `Table`, a dict that also says which nodes the LSDB held
drained; `compare` prints them beside the counts, so that a run's lines
show whether a compared table was of a fabric with a switch out.
"""

from __future__ import annotations

import numpy as np
import reference
from scipy.sparse import csr_matrix
from scipy.sparse.csgraph import dijkstra

Unsupported = reference.Unsupported


class Table(dict):
    drained: tuple = ()


def graph(adj_dbs: list):
    """-> (node index, every advertised adjacency, the weighted graph
    without the out-edges of drained nodes, the drained nodes)."""
    index = {db.this_node_name: i for i, db in enumerate(adj_dbs)}
    advertised, drained = {}, set()
    for db in adj_dbs:
        if db.node_metric_increment:
            raise Unsupported(f"{db.this_node_name} is soft-drained")
        if db.is_overloaded:
            drained.add(db.this_node_name)
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
        # a link is there where both ends advertise it, drained or not
        if (other, other_if, me, my_if) not in advertised or other not in index:
            continue
        if metric <= 0:
            raise Unsupported(f"metric {metric} on {me} -> {other}")
        if me in drained:
            continue  # no path leaves a drained node
        rows.append(index[me])
        cols.append(index[other])
        weights.append(metric)
    if len(set(zip(rows, cols))) != len(rows):
        raise Unsupported("parallel links between one pair of nodes")
    n = len(index)
    weighted = csr_matrix(
        (np.asarray(weights, np.float64), (rows, cols)), shape=(n, n)
    )
    return index, advertised, weighted, drained


def announcers(nodes: list, drained: set) -> list:
    """The advertisers a prefix is routed to: the drained ones dropped,
    unless all are drained."""
    return [n for n in nodes if n not in drained] or list(nodes)


def routes_of(adj_dbs: list, prefix_dbs: list, me: str, lfa: bool) -> Table:
    index, advertised, weighted, drained = graph(adj_dbs)
    if me not in index:
        raise Unsupported(f"vantage {me} is not in the LSDB")
    if me in drained:
        raise Unsupported(f"the vantage {me} is drained")
    # the vantage's verified links, in the order LFA breaks ties by
    links = []
    for (node, my_if, other, other_if), metric in advertised.items():
        if node == me and (other, other_if, me, my_if) in advertised:
            ends = sorted(((me, my_if), (other, other_if)))
            links.append((ends, other, my_if, metric))
    links.sort()
    sources = [index[me]] + [index[other] for _, other, _, _ in links]
    dist = dijkstra(weighted, directed=True, indices=sources)
    dist_me, dist_nbr = dist[0], dist[1:]

    advertisers: dict[str, list] = {}
    for db in prefix_dbs:
        if db.area != adj_dbs[0].area:
            raise Unsupported("more than one area")
        for entry in db.prefix_entries:
            advertisers.setdefault(entry.prefix, []).append(db.this_node_name)

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

    out = Table()
    out.drained = tuple(sorted(drained))
    for prefix, nodes in advertisers.items():
        if len(nodes) > 1:
            raise Unsupported(f"{prefix} has two advertisers")
        (name,) = announcers(nodes, drained)
        node = index.get(name, -1)
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


def routes(lsdb, me: str, config: dict) -> Table:
    lfa = bool(config.get("decision_config", {}).get("enable_lfa"))
    return routes_of(lsdb.adj_dbs, lsdb.prefix_dbs, me, lfa)


def programmed(snapshot: dict) -> dict:
    return reference.programmed(snapshot["unicast"])


def compare(got: dict, want: dict) -> dict:
    check = reference.compare(got, want)
    check["drained"] = list(getattr(want, "drained", ()))
    return check

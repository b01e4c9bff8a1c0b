"""The plain reference of a router inside one area of a multi-area WAN
(lsdbs/region.py): every prefix of another area reaches it from SEVERAL
advertisers, its area's border routers, so a route is decided by best-route
selection among advertisers and not by one Dijkstra distance. Independent of
the program's Decision code: scipy's Dijkstra, references/node_drain.py's
graph (what a link is, no path out of a drained router) and plain Python; the
form of a route, `programmed` and the comparison are reference.py's:

    prefix -> (metric, {(neighbour, interface, metric)},
               {(neighbour, interface, alternate metric)})

Distances. Dijkstra from the vantage v and from each of its neighbours N on
the area's graph WITHOUT THE OUT-EDGES OF EVERY DRAINED ROUTER (upstream
LinkState::runSpf: a path may end at an overloaded node, never pass
through it), as references/node_drain.py. A link is there where both ends
advertise it with matching interfaces, drained or not, at the metric each
end advertises for its direction.

Selection, per prefix, over its advertisers in this area, in upstream's
order (`select`, below, has each step beside its sentence): reachable;
highest path preference; highest source preference; lowest advertised
distance; drained advertisers dropped unless all are drained. What is left
is the set U forwarding may use. Then

- metric(p) = min over a in U of dist_v(a); the advertisers at that
  distance are the nearest ones;
- next hops = the vantage's links (v, N) that are a first hop of a shortest
  path to ANY nearest advertiser: w(v, N) + dist_N(a) == dist_v(a) (ECMP
  across advertisers, SpfSolver.cpp:1043-1089 getNextHopsWithMetric);
- no route where v itself is in U (SpfSolver.cpp:330-344).

The alternate, RFC 5286 section 6.1 (multi-homed prefixes): "a prefix p
[...] advertised by multiple routers [...] Distance_opt(N, p)" is the least
over the routers that advertise p. Link (v, N) is loop-free for p iff

    min over a in U of dist_N(a)  <  dist_N(v) + metric(p)

and the one alternate is the cheapest such link that is not a next hop, at
cost w(v, N) + min over a in U of dist_N(a); ties go to the link that sorts
first by (lower end, its interface, upper end, its interface), as
reference.py breaks them. `a` runs over ALL of U, the farther advertisers
among them too, and over nothing outside U. Why the RFC is read so: its
distance to a multi-homed prefix is the distance to wherever the neighbour
itself would deliver the packet. N runs the same selection on the same
LSDB, so it never delivers to an advertiser the selection ruled out (a
lower preference, a greater advertised distance, a drained router beside a
live one): those are no exits for p, however near N is to them. But N
forwards to ITS nearest member of U, which need not be v's nearest: an
advertiser farther from v is still a valid exit seen from N. (Upstream has
no LFA; the program's oracle, SpfSolver._lfa_candidates, reads the RFC the
same way, and this file does not import it.)

Refused, not guessed (`reference.Unsupported`): a prefix announced in two
of the vantage's areas (that needs selection across areas), a drained or
held adjacency, a soft drain (a non-zero `node_metric_increment`), a
drained vantage, parallel links, a non-positive metric, an entry that is
not IP / SP_ECMP or carries `min_nexthop`.

`routes` returns a `Table`, a dict that also says what the LSDB held away
from rest (the model's `held()`: a stepped link, a drained router) and, for
the routes to prefixes of other areas (every advertiser's entry carries an
`area_stack`), each distinct (metric, next hops, alternate) with the count
of routes that have it; `compare` prints both beside the counts, so a run's
lines show which table was compared.
"""

from __future__ import annotations

import math

import files
import reference
from scipy.sparse.csgraph import dijkstra

# what a link is, the graph without the out-edges of drained routers and
# what of an adjacency database is refused: references/node_drain.py's
graph = files.reference_module({"reference_module": "node_drain"}).graph
Unsupported = reference.Unsupported


class Table(dict):
    held: tuple = ()
    inter_area: tuple = ()


def advertisers(prefix_dbs: list, area: str, index: dict) -> dict:
    """prefix -> [(node number, name, path preference, source preference,
    advertised distance, has an area_stack)], the advertisers in `area`
    that are routers of its graph."""
    out: dict[str, list] = {}
    for db in prefix_dbs:
        if db.area != area:
            raise Unsupported(
                f"{db.this_node_name} announces in {db.area}, a second area "
                f"of the vantage: selection across areas"
            )
        if db.delete_prefix:
            continue
        node = index.get(db.this_node_name)
        for entry in db.prefix_entries:
            if (entry.forwarding_type.name != "IP"
                    or entry.forwarding_algorithm.name != "SP_ECMP"
                    or entry.min_nexthop is not None):
                raise Unsupported(f"{entry.prefix}: not plain IP / SP_ECMP")
            if node is None:
                continue  # not a router of this area: unreachable
            m = entry.metrics
            out.setdefault(entry.prefix, []).append((
                node, db.this_node_name, m.path_preference,
                m.source_preference, m.distance, bool(entry.area_stack),
            ))
    return out


def select(entries: list, dist_me: list, drained: set) -> list:
    """The advertisers forwarding may use (U), out of a prefix's entries."""
    # "drop announcers unreachable in their area" (SpfSolver.cpp:230-244)
    left = [e for e in entries if math.isfinite(dist_me[e[0]])]
    if not left:
        return []
    # "best (path_preference desc, source_preference desc)": the highest
    # path preference, and among those the highest source preference
    # (LsdbUtil.cpp selectRoutes:842, SHORTEST_DISTANCE)
    best = max(e[2] for e in left)
    left = [e for e in left if e[2] == best]
    best = max(e[3] for e in left)
    left = [e for e in left if e[3] == best]
    # "then min advertised distance" (the same function): the hops an
    # entry has been redistributed over, not an IGP metric
    best = min(e[4] for e in left)
    left = [e for e in left if e[4] == best]
    # "drop [...] drained announcers; if ALL are drained keep the
    # unfiltered set" (SpfSolver.cpp:709-731, maybeFilterDrainedNodes)
    return [e for e in left if e[1] not in drained] or left


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
    dist_me = dist[0].tolist()
    dist_nbr = [row.tolist() for row in dist[1:]]
    back = [row[index[me]] for row in dist_nbr]  # dist_N(v)

    out = Table()
    classes: dict[tuple, int] = {}
    for prefix, entries in advertisers(
        prefix_dbs, adj_dbs[0].area, index
    ).items():
        usable = select(entries, dist_me, drained)
        # "skip route for a prefix advertised by self" (SpfSolver.cpp:
        # 330-344)
        if not usable or any(e[0] == index[me] for e in usable):
            continue
        # the least IGP distance to a usable advertiser, and who is there
        metric = min(dist_me[e[0]] for e in usable)
        nearest = [e[0] for e in usable if dist_me[e[0]] == metric]
        cost = int(metric)
        primary = [
            any(w + dist_nbr[k][a] == metric for a in nearest)
            for k, (_, _, _, w) in enumerate(links)
        ]
        hops = frozenset(
            (other, my_if, cost)
            for k, (_, other, my_if, _) in enumerate(links) if primary[k]
        )
        backups = frozenset()
        if lfa:
            # RFC 5286 section 6.1: N's distance to the prefix is its
            # least distance to an advertiser forwarding may use
            best = None
            for k, (_, other, my_if, w) in enumerate(links):
                if primary[k]:
                    continue
                d = min(dist_nbr[k][e[0]] for e in usable)
                if d < back[k] + metric and (best is None or w + d < best[0]):
                    best = (w + d, other, my_if)  # the first of equal costs
            if best is not None:
                backups = frozenset({(best[1], best[2], int(best[0]))})
        out[prefix] = (cost, hops, backups)
        if all(e[5] for e in entries):
            shape = (cost, tuple(sorted(h[0] for h in hops)),
                     tuple(sorted((b[0], b[2]) for b in backups)))
            classes[shape] = classes.get(shape, 0) + 1
    out.inter_area = tuple(
        {"routes": count, "metric": shape[0], "next_hops": list(shape[1]),
         "alternate": [list(b) for b in shape[2]]}
        for shape, count in sorted(
            classes.items(), key=lambda item: -item[1]
        )[:4]
    )
    return out


def routes(lsdb, me: str, config: dict) -> Table:
    if len(lsdb.by_area) != 1:
        raise Unsupported("the vantage is in more than one area")
    lfa = bool(config.get("decision_config", {}).get("enable_lfa"))
    (sub,) = lsdb.by_area.values()
    out = routes_of(sub.adj_dbs, sub.prefix_dbs, me, lfa)
    out.held = tuple(lsdb.held())
    return out


def programmed(snapshot: dict) -> dict:
    return reference.programmed(snapshot["unicast"])


def compare(got: dict, want: dict) -> dict:
    check = reference.compare(got, want)
    check["held"] = list(getattr(want, "held", ()))
    check["inter_area"] = list(getattr(want, "inter_area", ()))
    return check

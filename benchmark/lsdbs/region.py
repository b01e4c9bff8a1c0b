"""One area of a multi-area WAN, as a router inside it holds it: the
generator's network (`wan_rtt`: regions of core, aggregation and access
routers) cut into an area a region and a backbone, what every border router
redistributes derived by rule, and the vantage's ONE area served under its
own name (`r25`) with the operations of lsdb.py (`metric`, `down`, `up`) and
of lsdbs/node_drain.py (`drain`, `undrain`).

The areas (openr/docs/Features/Area.md: an area a region, a backbone
between them):

- a link between two core routers, of one region or of two, is a backbone
  link and lies in `bb`; every other link lies in its region's area `r<g>`;
- a core router is therefore in two areas, `r<g>` and `bb`, and is its
  region's border router; every other router is in `r<g>` alone;
- every router originates its prefixes in each area it is in.

The rule a border router redistributes by (upstream PrefixManager.cpp:
1662-1765, `redistributePrefixesAcrossAreas`; written out here and in
`transit`, not imported: benchmark/tests and tests/test_wan_region.py hold
the result equal to what the program's own PrefixManager gives):

- it re-advertises each route it has PROGRAMMED into each of its areas that
  none of the route's next hops lies in, never into an area on the entry's
  `area_stack`, never a prefix it originates itself;
- the re-advertised entry is the route's best entry with type RIB, distance
  + 1, the area the route came from appended to `area_stack`, IP / SP_ECMP,
  and the non-transitive attributes (min_nexthop, prepend_label, weight)
  reset. It carries no IGP cost: a metric that moves inside another area
  reaches this area as nothing.

A border router's route to a prefix is chosen as Decision chooses it
(highest path and source preference, lowest advertised distance, then the
areas of lowest IGP distance). With every router advertising the generator's
default preferences that gives, for a border router B of the vantage's
region V:

- the prefix of a router X of another region g that is in no backbone: the
  border routers of g hold it in `r<g>` (distance 0) and re-advertise it
  into `bb` (distance 1, stack (r<g>)); B's best are those (the copies the
  other border routers of V put into V read distance 2), its next hops lie
  in `bb`, and it re-advertises into V at distance 2, stack (r<g>, bb);
- the prefix of a border router Y of another region: native in `bb` at
  distance 0, which beats every re-advertised copy: B re-advertises it into
  V at distance 1, stack (bb);
- the prefix of another border router B' of V itself: native in V and in
  `bb`, both at distance 0, so the IGP distance decides: where B is nearer
  to B' through `bb` than through V its next hops lie in `bb` alone and it
  re-advertises into V at distance 1, stack (bb); where V is nearer or they
  tie, nothing goes into V;
- a prefix native in V and in no backbone: B's route lies in V; nothing
  comes back (V is on the stack of every copy).

`bb` reaches as far as its links go without transit through a drained
router (a drained border router is drained in both its areas; its own
Decision exempts itself, so it keeps redistributing). What the other areas
hold stays with the generator: no router floods it here. So EVERY `apply`
checks that the operation changes nothing a border router of V
redistributes (each still reaches every other region's border routers
through `bb`, and still prefers `bb` towards the same border routers of V),
and refuses the operation otherwise.

`key_vals` gives one key a (node, area, prefix), as upstream's per-prefix
keys: one `PrefixDatabase` an entry, as the generator makes them.
"""

from __future__ import annotations

import re
from dataclasses import replace

import numpy as np
from scipy.sparse import csr_matrix
from scipy.sparse.csgraph import connected_components, dijkstra

import files
import lsdb

node_drain = files.lsdb_module({"lsdb_module": "node_drain"})

BACKBONE = "bb"
NAME = re.compile(r"^(r\d+)-(core|agg|acc)\d+$")


def region_of(node: str) -> str:
    return NAME.match(node).group(1)


def is_border(node: str) -> bool:
    return NAME.match(node).group(2) == "core"


def transit(entry, came_from: str):
    """The entry a border router re-advertises for a route whose best entry
    is `entry` and whose next hops lie in area `came_from`."""
    from openr_tpu.types import (
        PrefixForwardingAlgorithm, PrefixForwardingType, PrefixType,
    )

    return replace(
        entry,
        type=PrefixType.RIB,
        metrics=replace(entry.metrics, distance=entry.metrics.distance + 1),
        area_stack=tuple(entry.area_stack) + (came_from,),
        forwarding_type=PrefixForwardingType.IP,
        forwarding_algorithm=PrefixForwardingAlgorithm.SP_ECMP,
        min_nexthop=None, prepend_label=None, weight=None,
    )


def _distances(dbs: list, keep, sources: list[str], drained: set) -> dict:
    """{source: {node: distance}} over the links of `dbs` that `keep(a, b)`
    admits, each direction at the metric its end advertises, with no
    transit through a drained router (no path leaves one, but the source's
    own: a router's Decision exempts itself from its bit)."""
    names = [db.this_node_name for db in dbs]
    index = {name: i for i, name in enumerate(names)}
    src, dst, w = [], [], []
    for db in dbs:
        for adj in db.adjacencies:
            if adj.other_node_name in index and keep(
                db.this_node_name, adj.other_node_name
            ):
                src.append(index[db.this_node_name])
                dst.append(index[adj.other_node_name])
                w.append(adj.metric)
    src, dst = np.asarray(src, np.int64), np.asarray(dst, np.int64)
    w = np.asarray(w, np.float64)
    out_of_drained = np.isin(src, [index[n] for n in drained if n in index])
    out = {}
    for source in sources:
        live = ~out_of_drained | (src == index[source])
        dist = dijkstra(
            csr_matrix((w[live], (src[live], dst[live])),
                       shape=(len(names), len(names))),
            directed=True, indices=index[source],
        )
        out[source] = dict(zip(names, dist.tolist()))
    return out


class Network:
    """The generator's network cut into areas, as it stands at rest: what
    `replay` shares with the model it was made from."""

    def __init__(self, adj_dbs: list, prefix_dbs: list, vantage: str):
        for db in adj_dbs:
            if NAME.match(db.this_node_name) is None:
                raise ValueError(f"{db.this_node_name}: not a wan_rtt name")
        if is_border(vantage):
            raise ValueError(
                f"{vantage} is a border router: it holds two areas and "
                "selects across them, which this model does not serve"
            )
        self.vantage = vantage
        self.area = region_of(vantage)
        self.regions = sorted({region_of(db.this_node_name) for db in adj_dbs})
        self.areas = len(self.regions) + 1
        self.routers = len(adj_dbs)
        self.borders = sorted(
            db.this_node_name for db in adj_dbs
            if is_border(db.this_node_name)
        )
        self.my_borders = [b for b in self.borders
                           if region_of(b) == self.area]
        if not self.my_borders:
            raise ValueError(f"area {self.area} has no border router")
        self.backbone_dbs = [
            db for db in adj_dbs if is_border(db.this_node_name)
        ]
        self._check_cut(adj_dbs)
        # the vantage's area: its routers' databases, less the backbone
        # links of its border routers
        self.adj_dbs = [
            replace(db, area=self.area, adjacencies=tuple(
                adj for adj in db.adjacencies
                if self.area_of_link(db.this_node_name,
                                     adj.other_node_name) == self.area
            ))
            for db in adj_dbs if region_of(db.this_node_name) == self.area
        ]
        self.sent = self.redistributed(self.adj_dbs, set())
        for b, (_, foreign, _) in self.sent.items():
            missing = set(self.borders) - set(self.my_borders) - foreign
            if missing:
                raise ValueError(
                    f"{b} does not reach {sorted(missing)[0]} through "
                    f"{BACKBONE}: the rule has no entry for its prefix"
                )
        self.prefix_dbs = self._prefix_dbs(prefix_dbs)

    @staticmethod
    def area_of_link(a: str, b: str) -> str:
        if is_border(a) and is_border(b):
            return BACKBONE
        if region_of(a) != region_of(b):
            raise ValueError(f"{a} - {b} joins two regions outside {BACKBONE}")
        return region_of(a)

    def _check_cut(self, adj_dbs: list) -> None:
        """Every region's area is connected on its own links: its border
        routers reach every router of it, as the rule takes for granted."""
        index = {db.this_node_name: i for i, db in enumerate(adj_dbs)}
        src, dst = [], []
        for db in adj_dbs:
            for adj in db.adjacencies:
                a, b = db.this_node_name, adj.other_node_name
                if self.area_of_link(a, b) != BACKBONE:
                    src.append(index[a])
                    dst.append(index[b])
        n = len(index)
        _, label = connected_components(
            csr_matrix((np.ones(len(src)), (src, dst)), shape=(n, n)),
            directed=False,
        )
        parts: dict[str, set] = {}
        for name, i in index.items():
            parts.setdefault(region_of(name), set()).add(int(label[i]))
        split = sorted(r for r, labels in parts.items() if len(labels) > 1)
        if split:
            raise ValueError(f"area {split[0]} is not connected")

    def redistributed(self, area_dbs: list, drained: set) -> dict:
        """What each border router B of the vantage's area puts into it, as
        far as an operation on the area can move it: {B: (the regions it
        reaches a border router of through `bb`, the border routers of
        other regions it reaches there, the border routers of its own
        region it is nearer to through `bb` than through the area)}."""
        in_bb = _distances(
            self.backbone_dbs, lambda a, b: is_border(b), self.my_borders,
            drained,
        )
        in_area = _distances(
            area_dbs, lambda a, b: True, self.my_borders, drained
        )
        out = {}
        for b in self.my_borders:
            foreign = frozenset(
                y for y in self.borders
                if region_of(y) != self.area and np.isfinite(in_bb[b][y])
            )
            out[b] = (
                frozenset(region_of(y) for y in foreign),
                foreign,
                frozenset(
                    o for o in self.my_borders
                    if o != b and in_bb[b][o] < in_area[b][o]
                ),
            )
        return out

    def _prefix_dbs(self, prefix_dbs: list) -> list:
        """The area's prefix databases at rest, one an entry: the native
        ones, then what each border router redistributes, by the rule."""
        from openr_tpu.types import PrefixDatabase

        native = [
            replace(db, area=self.area) for db in prefix_dbs
            if region_of(db.this_node_name) == self.area
        ]
        # each foreign prefix as a border router of the area re-advertises
        # it: the entry is the same whichever of them does
        carried: dict[str, list] = {}
        for db in prefix_dbs:
            node = db.this_node_name
            if region_of(node) == self.area and not is_border(node):
                continue
            for entry in db.prefix_entries:
                if not is_border(node):
                    entry = transit(entry, region_of(node))
                carried.setdefault(node, []).append(transit(entry, BACKBONE))
        out = list(native)
        for b in self.my_borders:
            regions, foreign, own = self.sent[b]
            for node, entries in carried.items():
                if region_of(node) == self.area:
                    sends = node in own
                elif is_border(node):
                    sends = node in foreign
                else:
                    sends = region_of(node) in regions
                if not sends:
                    continue
                out.extend(
                    PrefixDatabase(
                        this_node_name=b, prefix_entries=(entry,),
                        area=self.area,
                    )
                    for entry in entries
                )
        return out


class RegionArea(node_drain.NodeDrain):
    """The vantage's area: lsdbs/node_drain.py's model under the area's own
    name."""

    def __init__(self, adj_dbs: list, prefix_dbs: list):
        super().__init__(adj_dbs, prefix_dbs)
        self.area = adj_dbs[0].area

    def areas(self) -> list[str]:
        return [self.area]

    def key_vals(self) -> dict:
        return {self.area: lsdb.Lsdb.key_vals(self)}

    def publication(self, changed: list[str]) -> dict:
        return {self.area: lsdb.Lsdb.publication(self, changed)}


class Region:
    def __init__(self, network: Network):
        self.network = network
        self.sub = RegionArea(network.adj_dbs, network.prefix_dbs)
        self.by_area = {network.area: self.sub}
        self.index = self.sub.index
        self.log = self.sub.log

    # what the kinds and the reference read: the served area's
    adj_dbs = property(lambda self: self.sub.adj_dbs)
    prefix_dbs = property(lambda self: self.sub.prefix_dbs)
    drained = property(lambda self: self.sub.drained)

    def areas(self) -> list[str]:
        return self.sub.areas()

    def links(self) -> set[tuple[str, str]]:
        return self.sub.links()

    def neighbors(self, node: str) -> list[str]:
        return self.sub.neighbors(node)

    def key_vals(self) -> dict:
        return self.sub.key_vals()

    def held(self) -> list:
        """What the operations applied so far hold away from the area at
        rest: ["metric", a, b, m], ["down", a, b], ["drain", node]."""
        out = []
        for a, others in sorted(self.sub._metric.items()):
            base = {adj.other_node_name: adj.metric
                    for adj in self.sub._base[a].adjacencies}
            out.extend(
                ["metric", a, b, m] for b, m in sorted(others.items())
                if a < b and m != base[b]
            )
        out.extend(
            ["down", a, b] for a, others in sorted(self.sub._down.items())
            for b in sorted(others) if a < b
        )
        out.extend(["drain", node] for node in sorted(self.sub.drained))
        return out

    def apply(self, ops: list) -> list[str]:
        """-> the nodes of the area whose adjacency database changed. An
        operation that would change what a border router redistributes is
        refused (ValueError): the model has no entries for that."""
        changed = self.sub.apply(ops)
        now = self.network.redistributed(self.sub.adj_dbs, self.sub.drained)
        if now != self.network.sent:
            moved = sorted(b for b in now if now[b] != self.network.sent[b])
            raise ValueError(
                f"{ops!r} changes what {moved[0]} redistributes into "
                f"{self.network.area}: refused"
            )
        return changed

    def publication(self, changed: list[str]) -> dict:
        return self.sub.publication(changed)

    def replay(self, batches: int) -> "Region":
        then = Region(self.network)
        for ops in self.log[:batches]:
            then.apply(ops)
        return then


def build(config: dict) -> Region:
    model = Region(Network(*lsdb.generate(config), config["vantage"]))
    if config["vantage"] not in model.index:
        raise ValueError(f"vantage {config['vantage']} is not in its area")
    return model

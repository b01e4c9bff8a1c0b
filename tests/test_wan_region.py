"""One area of a multi-area WAN from inside it (benchmark configuration
wan50k_region): every prefix of another area reaches the vantage from
SEVERAL advertisers, its area's border routers, so a route is decided by
best-route selection among them.

At small size on the CPU (`wan_rtt` at 3 regions of 2 core, 3 aggregation
and 6 access routers; 4 core routers where a row must hold four
advertisers):

  (a) the program, through `TpuSpfSolver` and through the CPU `SpfSolver`,
      against the benchmark's plain reference (benchmark/references/
      region.py) on seeded LSDBs made by the benchmark's model
      (benchmark/lsdbs/region.py): at rest, with each uplink of the vantage
      stepped, with each border router drained, LFA on and off: prefix,
      metric, next-hop set, alternate;
  (b) THE MODEL'S REDISTRIBUTION TIED TO THE PROGRAM'S: the program's own
      Decision (`SpfSolver` over a border router's two areas) and
      `PrefixManager._redistribute_across_areas`, run for every border
      router of the network to a fixed point, advertise into each region's
      area exactly the entries the model derives by rule (advertiser,
      prefix, type, distance, `area_stack`, every other field);
  (c) the reference refuses what it says it refuses, and the model an
      operation that would change what a border router redistributes;
  (d) a prefix event and an overload flip on a row with four advertisers
      give the same rows from the prefix-only program, from the candidates'
      path and from the all-rows text (tests/test_compact_rows.Recorder
      replays every dispatch through the all-rows pipeline, word for word),
      and the gauges and the stamped counter the configuration added read
      what the matrix holds.
"""

import os
import sys
from dataclasses import replace

import pytest

from openr_tpu.decision import tpu_solver as ts
from openr_tpu.decision.rib import DecisionRouteUpdate
from openr_tpu.decision.spf_solver import SpfSolver
from openr_tpu.decision.tpu_solver import TpuSpfSolver
from openr_tpu.messaging import ReplicateQueue
from openr_tpu.models import topologies
from openr_tpu.prefix_manager.prefix_manager import PrefixManager
from openr_tpu.runtime.counters import counters
from openr_tpu.types import (
    PrefixDatabase,
    PrefixEntry,
    PrefixForwardingAlgorithm,
    PrefixType,
)
from tests.test_compact_rows import Recorder
from tests.test_tpu_solver import assert_rib_equal

BENCH = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmark"
)
SMALL = {"regions": 3, "cores": 2, "aggs": 3, "access": 6}
FOUR = {"regions": 3, "cores": 4, "aggs": 4, "access": 6}
ME = "r00-acc0004"


def config(shape: dict, seed: int = 7, me: str = ME, lfa: bool = True):
    return {
        "generator": {"call": "wan_rtt", "args": [],
                      "kwargs": {**shape, "seed": seed}},
        "vantage": me, "solver_backend": "tpu",
        "decision_config": {"enable_lfa": lfa},
        "lsdb_module": "region", "reference_module": "region",
    }


@pytest.fixture(scope="module")
def bench():
    """The benchmark's own modules, found the way run.py finds them."""
    sys.path.insert(0, BENCH)
    try:
        import files
        import reference

        class Bench:
            model = files.lsdb_module(config(SMALL))
            ref = files.reference_module(config(SMALL))
            plain = reference

        yield Bench
    finally:
        sys.path.remove(BENCH)


def programmed(bench, route_db) -> dict:
    return bench.ref.programmed({"unicast": dict(route_db.unicast_routes)})


def uplinks(lsdb, me: str) -> list:
    (db,) = [db for db in lsdb.adj_dbs if db.this_node_name == me]
    return [(adj.other_node_name, adj.metric) for adj in db.adjacencies]


# -- (a) the program against the reference -----------------------------------

STATES = ["rest", "uplink0", "uplink1", "border0", "border1"]


def held_ops(lsdb, me: str, state: str) -> list:
    if state == "rest":
        return []
    k = int(state[-1])
    if state.startswith("uplink"):
        other, metric = uplinks(lsdb, me)[k]
        return [("metric", me, other, 2 * metric + 1)]
    return [("drain", lsdb.network.my_borders[k])]


@pytest.mark.parametrize("state", STATES)
@pytest.mark.parametrize("lfa", [True, False], ids=["lfa", "plain"])
@pytest.mark.parametrize("solver", ["tpu", "cpu"])
@pytest.mark.parametrize("seed", [7, 11])
def test_the_program_holds_the_references_table(bench, seed, solver, lfa,
                                                state):
    cfg = config(SMALL, seed, lfa=lfa)
    lsdb = bench.model.build(cfg)
    ops = held_ops(lsdb, ME, state)
    if ops:
        lsdb.apply(ops)
    want = bench.ref.routes(lsdb, ME, cfg)
    # 10 routers of the area but the vantage, and the 22 of the two others
    assert len(want) == 32 and list(want.held) == lsdb.held()
    assert sum(c["routes"] for c in want.inter_area) == 22
    states, ps = topologies.build_states(lsdb.adj_dbs, lsdb.prefix_dbs)
    make = TpuSpfSolver if solver == "tpu" else SpfSolver
    got = programmed(
        bench, make(ME, enable_lfa=lfa).build_route_db(ME, states, ps)
    )
    check = bench.ref.compare(got, want)
    assert (check["routes_compared"], check["missing"], check["extra"],
            check["differing"]) == (32, 0, 0, 0), check
    assert check["held"] == lsdb.held() and check["inter_area"]
    if not lfa:
        assert not any(alt for _, _, alt in want.values())
    # and the change was one: the table differs from the one at rest where
    # the kind's rule says this vantage's exits shift
    if state != "rest" and seed == 7:
        rest = bench.ref.routes(lsdb.replay(0), ME, cfg)
        moved = {p for p in want if want[p] != rest[p]}
        assert (len(moved) >= 22) == (state != "border0"), (state, len(moved))


def test_a_step_moves_every_inter_area_route_and_a_give_back_returns_it(
    bench,
):
    """One solver through the cell's cycle: each table the reference's,
    the table at rest again after every give-back, entry for entry."""
    cfg = config(SMALL)
    lsdb = bench.model.build(cfg)
    states, ps = topologies.build_states(lsdb.adj_dbs, lsdb.prefix_dbs)
    tpu = TpuSpfSolver(ME, enable_lfa=True)
    rest = programmed(bench, tpu.build_route_db(ME, states, ps))
    assert rest == bench.ref.routes(lsdb, ME, cfg)
    (a, ma), (b, mb) = uplinks(lsdb, ME)
    border = lsdb.network.my_borders[1]
    for ops, back in (
        ([("metric", ME, a, 3 * ma)], [("metric", ME, a, ma)]),
        ([("drain", border)], [("undrain", border)]),
        ([("metric", ME, b, 2 * mb)], [("metric", ME, b, mb)]),
    ):
        for step in (ops, back):
            for node in lsdb.apply(step):
                states[lsdb.areas()[0]].update_adjacency_database(
                    lsdb.adj_dbs[lsdb.index[node]]
                )
            got = programmed(bench, tpu.build_route_db(ME, states, ps))
            want = bench.ref.routes(lsdb, ME, cfg)
            assert got == want, step
            assert (got == rest) == (step is back), step
            moved = {p for p in got if got[p] != rest[p]}
            assert step is back or len(moved) >= 22, (step, len(moved))


# -- (b) the model's entries are the program's own redistribution ------------


def program_redistribution(adj_dbs: list, prefix_dbs: list, model) -> dict:
    """{area: {(advertiser, prefix): PrefixEntry}}: what every router
    advertises once every border router's Decision and PrefixManager (the
    program's own) have run to a fixed point. The cut into areas is the
    model's (`area_of_link`); every entry but the native ones is the
    program's."""
    from openr_tpu.decision.link_state import LinkState
    from openr_tpu.decision.prefix_state import PrefixState

    areas_of = {}
    area_dbs: dict[str, list] = {}
    for db in adj_dbs:
        node = db.this_node_name
        mine = {}
        for adj in db.adjacencies:
            area = model.Network.area_of_link(node, adj.other_node_name)
            mine.setdefault(area, []).append(adj)
        areas_of[node] = sorted(mine)
        for area, adjs in mine.items():
            area_dbs.setdefault(area, []).append(
                replace(db, area=area, adjacencies=tuple(adjs))
            )
    native = {
        area: {
            (db.this_node_name, e.prefix): e
            for db in prefix_dbs for e in db.prefix_entries
            if area in areas_of[db.this_node_name]
        }
        for area in area_dbs
    }
    borders = sorted(n for n, areas in areas_of.items() if len(areas) == 2)
    sent: dict[str, dict] = {b: {} for b in borders}  # b -> {(area, prefix)}

    def adverts(area: str) -> dict:
        out = dict(native[area])
        for b, entries in sent.items():
            for (dst, prefix), entry in entries.items():
                if dst == area:
                    out[(b, prefix)] = entry
        return out

    for round_ in range(12):
        changed = False
        for b in borders:
            states, ps = {}, PrefixState()
            for area in areas_of[b]:
                states[area] = LinkState(area)
                for db in area_dbs[area]:
                    states[area].update_adjacency_database(db)
                for (node, _), entry in adverts(area).items():
                    ps.update_prefix_database(
                        PrefixDatabase(node, (entry,), area)
                    )
            rib = SpfSolver(b).build_route_db(b, states, ps)
            pm = PrefixManager(
                b, areas_of[b], ReplicateQueue("p").get_reader(), None,
                ReplicateQueue("kv"),
            )
            now = {}
            pm.advertise_prefixes = lambda entries, ptype, dst, now=now: (
                now.update({(a, e.prefix): e for a in dst for e in entries})
            )
            pm._redistribute_across_areas(DecisionRouteUpdate(
                unicast_routes_to_update=dict(rib.unicast_routes)
            ))
            assert all(e.type == PrefixType.RIB for e in now.values())
            if now != sent[b]:
                sent[b], changed = now, True
        if not changed:
            assert round_ >= 2  # it took a round to cross the backbone
            return {area: adverts(area) for area in area_dbs}
    raise AssertionError("redistribution did not settle in 12 rounds")


@pytest.fixture(scope="module", params=[SMALL, FOUR], ids=["2cores", "4cores"])
def settled(request, bench):
    shape = request.param
    adj_dbs, prefix_dbs = topologies.wan_rtt(**shape, seed=7)
    return shape, program_redistribution(adj_dbs, prefix_dbs, bench.model)


@pytest.mark.parametrize("region", ["r00", "r01", "r02"])
def test_the_models_entries_are_what_the_programs_prefix_manager_advertises(
    bench, settled, region,
):
    shape, by_area = settled
    me = f"{region}-acc0000"
    lsdb = bench.model.build(config(shape, me=me))
    assert lsdb.areas() == [region]
    mine = {
        (db.this_node_name, e.prefix): e
        for db in lsdb.prefix_dbs for e in db.prefix_entries
    }
    assert len(mine) == len(lsdb.prefix_dbs)  # one database an entry
    assert all(db.area == region for db in lsdb.prefix_dbs)
    want = by_area[region]
    assert mine.keys() == want.keys()
    assert mine == want  # type, distance, area_stack and every other field
    cores, per = shape["cores"], sum(shape.values()) - shape["regions"]
    far = [e for e in mine.values() if e.type == PrefixType.RIB]
    # every prefix of the two other regions from every border router, and
    # a border router's own from those of the others nearer to it by bb
    own = len(far) - 2 * per * cores
    assert 0 <= own <= cores * (cores - 1)
    assert {(e.metrics.distance, len(e.area_stack)) for e in far} == {
        (1, 1), (2, 2)}
    assert all(e.area_stack[-1] == "bb" for e in far)
    assert all(
        e.forwarding_algorithm == PrefixForwardingAlgorithm.SP_ECMP
        for e in far
    )


# -- (c) what is refused ------------------------------------------------------


def _two_areas(lsdb):
    lsdb.sub.prefix_dbs.append(
        replace(lsdb.prefix_dbs[0], area="bb")
    )


def _drained_adjacency(lsdb):
    db = lsdb.adj_dbs[0]
    lsdb.sub.adj_dbs[0] = replace(db, adjacencies=(
        replace(db.adjacencies[0], is_overloaded=True),
    ) + db.adjacencies[1:])


def _soft_drain(lsdb):
    lsdb.sub.adj_dbs[3] = replace(lsdb.adj_dbs[3], node_metric_increment=5)


def _drained_vantage(lsdb):
    i = lsdb.index[ME]
    lsdb.sub.adj_dbs[i] = replace(lsdb.adj_dbs[i], is_overloaded=True)


def _parallel_links(lsdb):
    db = lsdb.adj_dbs[0]
    other = next(d for d in lsdb.adj_dbs
                 if d.this_node_name == db.adjacencies[0].other_node_name)
    back = next(a for a in other.adjacencies
                if a.other_node_name == db.this_node_name)
    lsdb.sub.adj_dbs[0] = replace(db, adjacencies=db.adjacencies + (
        replace(db.adjacencies[0], if_name="second",
                other_if_name="second"),
    ))
    lsdb.sub.adj_dbs[lsdb.index[other.this_node_name]] = replace(
        other, adjacencies=other.adjacencies + (
            replace(back, if_name="second", other_if_name="second"),
        ))


def _zero_metric(lsdb):
    db = lsdb.adj_dbs[0]
    lsdb.sub.adj_dbs[0] = replace(db, adjacencies=(
        replace(db.adjacencies[0], metric=0),
    ) + db.adjacencies[1:])


def _ksp2_entry(lsdb):
    db = lsdb.prefix_dbs[1]
    lsdb.sub.prefix_dbs[1] = replace(db, prefix_entries=(replace(
        db.prefix_entries[0],
        forwarding_algorithm=PrefixForwardingAlgorithm.KSP2_ED_ECMP,
    ),))


def _min_nexthop(lsdb):
    db = lsdb.prefix_dbs[1]
    lsdb.sub.prefix_dbs[1] = replace(db, prefix_entries=(replace(
        db.prefix_entries[0], min_nexthop=2,
    ),))


def _second_served_area(lsdb):
    lsdb.by_area["bb"] = lsdb.sub


REFUSED = {
    "a prefix in two areas": (_two_areas, "second area"),
    "a drained adjacency": (_drained_adjacency, "drained adjacency"),
    "a soft drain": (_soft_drain, "soft-drained"),
    "a drained vantage": (_drained_vantage, "vantage"),
    "parallel links": (_parallel_links, "parallel"),
    "a zero metric": (_zero_metric, "metric 0"),
    "a KSP2 entry": (_ksp2_entry, "SP_ECMP"),
    "min_nexthop": (_min_nexthop, "SP_ECMP"),
    "two served areas": (_second_served_area, "more than one area"),
}


@pytest.mark.parametrize("what", sorted(REFUSED))
def test_the_reference_refuses(bench, what):
    cfg = config(SMALL)
    lsdb = bench.model.build(cfg)
    assert len(bench.ref.routes(lsdb, ME, cfg)) == 32
    spoil, says = REFUSED[what]
    spoil(lsdb)
    with pytest.raises(bench.plain.Unsupported, match=says):
        bench.ref.routes(lsdb, ME, cfg)


def test_the_reference_is_independent_of_the_programs_decision():
    for name in ("lsdbs/region.py", "references/region.py",
                 "traffic_kinds/exit_shift.py"):
        with open(os.path.join(BENCH, name)) as f:
            text = f.read()
        lines = [l for l in text.splitlines()
                 if l.lstrip().startswith(("import ", "from "))]
        assert lines and not any(
            "openr_tpu.decision" in l or "openr_tpu.prefix_manager" in l
            for l in lines
        ), name


@pytest.mark.parametrize("out, says", [
    (("r00-core0", "r00-core1", "r00-core2"), None),
    # r00-core2 has no backbone link of its own out of the region: with the
    # three others drained it reaches no other region's border router
    (("r00-core0", "r00-core1", "r00-core3"), "r00-core2 redistributes"),
])
def test_the_model_refuses_what_would_change_a_border_routers_entries(
    bench, out, says,
):
    """Border routers drained while every other still reaches every region
    through the backbone are applied; the drain that cuts one off from it
    is refused: the model has no entries for that LSDB."""
    lsdb = bench.model.build(config(FOUR))
    lsdb.apply([("drain", node) for node in out[:2]])
    if says is None:
        lsdb.apply([("drain", out[2])])
        assert lsdb.drained == set(out)
        assert lsdb.replay(2).held() == lsdb.held() == [
            ["drain", node] for node in out]
        assert lsdb.replay(1).drained == set(out[:2])
        return
    with pytest.raises(ValueError, match=says):
        lsdb.apply([("drain", out[2])])


def test_a_border_router_is_no_vantage_of_this_model(bench):
    with pytest.raises(ValueError, match="border router"):
        bench.model.build(config(SMALL, me="r00-core0"))


# -- (d) rows with four advertisers ------------------------------------------


def counter(key: str) -> float:
    return counters.get_counter(key) or 0


class FourWide:
    """Both solvers over an area whose remote prefixes have four
    advertisers, every dispatch of the TPU solver replayed through the
    all-rows pipeline (Recorder) and every table held to the oracle's and
    to the reference's."""

    def __init__(self, bench, monkeypatch, lfa: bool):
        self.bench = bench
        self.cfg = config(FOUR, lfa=lfa)
        self.lsdb = bench.model.build(self.cfg)
        self.area = self.lsdb.areas()[0]
        self.states, self.ps = topologies.build_states(
            self.lsdb.adj_dbs, self.lsdb.prefix_dbs
        )
        self.cpu = SpfSolver(ME, enable_lfa=lfa)
        self.tpu = TpuSpfSolver(ME, enable_lfa=lfa, incremental_spf=True)
        self.rec = Recorder(monkeypatch, self.tpu)

    def solve(self, ctx: str, prefix_dbs=None) -> dict:
        want = self.cpu.build_route_db(ME, self.states, self.ps)
        got = self.tpu.build_route_db(ME, self.states, self.ps)
        assert_rib_equal(want, got, ctx)
        ref = self.bench.ref.routes_of(
            self.lsdb.adj_dbs, prefix_dbs or self.lsdb.prefix_dbs, ME,
            self.cfg["decision_config"]["enable_lfa"],
        )
        assert programmed(self.bench, got) == ref, ctx
        return self.tpu.last_device_stats


@pytest.mark.parametrize("lfa", [True, False], ids=["lfa", "plain"])
def test_a_prefix_event_on_a_four_wide_row_reads_the_same_on_every_path(
    bench, monkeypatch, lfa,
):
    w = FourWide(bench, monkeypatch, lfa)
    w.solve("the first table")
    matrix = w.tpu._area_dev[w.area].matrix
    assert matrix.ann_node.shape[1] == 4
    far = [db for db in w.lsdb.prefix_dbs
           if db.prefix_entries[0].area_stack]
    assert matrix.n_cells == len(w.lsdb.prefix_dbs) and matrix.n_multi >= 28
    # the vantage's nearest border router withdraws one remote prefix, then
    # advertises it again at a worse path preference, then as it was: the
    # row's selection changes each time, and each epoch is the prefix-only
    # program's, its rows the all-rows pipeline's word for word
    route = w.tpu.build_route_db(ME, w.states, w.ps).unicast_routes
    victim = next(
        db for db in far if db.prefix_entries[0].metrics.distance == 2
        and (db.this_node_name, w.area)
        == route[db.prefix_entries[0].prefix].best_node_area
    )
    entry = victim.prefix_entries[0]
    others = [db for db in w.lsdb.prefix_dbs if db is not victim]
    only = counter("decision.tpu.prefix_only_epochs")
    cells = matrix.n_cells
    for step, dbs in (
        ("withdrawn", others),
        ("worse", others + [replace(victim, prefix_entries=(replace(
            entry, metrics=replace(entry.metrics, path_preference=900)),))]),
        ("as it was", others + [victim]),
    ):
        if step == "withdrawn":
            w.ps.update_prefix_database(replace(victim, delete_prefix=True))
        else:
            w.ps.update_prefix_database(dbs[-1])
        stats = w.solve(step, dbs)
        variant = w.rec.epochs[-1][0]
        assert variant.rows_only and stats["prefix_only"], step
        assert matrix.n_cells == cells - (step == "withdrawn"), step
        assert counter("decision.tpu.announcer_cells") == matrix.n_cells
        assert counter("decision.tpu.announcer_slots") == 4
        assert counter("decision.tpu.multi_announcer_rows") == matrix.n_multi
    assert counter("decision.tpu.prefix_only_epochs") == only + 3


@pytest.mark.parametrize("lfa", [True, False], ids=["lfa", "plain"])
def test_an_overload_flip_on_four_wide_rows_reads_the_same_on_every_path(
    bench, monkeypatch, lfa,
):
    w = FourWide(bench, monkeypatch, lfa)
    w.solve("the first table")
    p_cap = w.tpu._area_dev[w.area].matrix.ann_node.shape[0]
    borders = w.lsdb.network.my_borders
    assert len(borders) == 4
    looked = []
    for node in borders[:2]:
        for op in ("drain", "undrain"):
            for changed in w.lsdb.apply([(op, node)]):
                w.states[w.area].update_adjacency_database(
                    w.lsdb.adj_dbs[w.lsdb.index[changed]]
                )
            stats = w.solve(f"{op} {node}")
            variant = w.rec.epochs[-1][0]
            assert variant.narrow and stats["incremental"], (op, node)
            looked.append(w.rec.looked[-1])
    # the candidates' path took them: the rows the drained router's cells
    # lie in, each of which holds three more advertisers
    assert all(0 < n < p_cap for n in looked), looked


def test_a_full_result_on_four_wide_rows_is_counted_and_stamped(
    bench, monkeypatch,
):
    """Past the delta budget an uplink's step lands as a journaled change,
    and `decision.tpu.full_changed_rows` gains the rows it journaled: what
    benchmark/layer_metrics/routes_moved_per_epoch.py reads."""
    monkeypatch.setattr(ts, "_DELTA_BUDGET", 16)
    w = FourWide(bench, monkeypatch, True)
    w.solve("the first table")
    gained = counter("decision.tpu.full_changed_rows")
    wide = counter("decision.tpu.wide_epochs")
    (a, ma), _ = uplinks(w.lsdb, ME)
    for node in w.lsdb.apply([("metric", ME, a, 3 * ma)]):
        w.states[w.area].update_adjacency_database(
            w.lsdb.adj_dbs[w.lsdb.index[node]]
        )
    stats = w.solve("an uplink stepped")
    assert stats["full_pull"] and stats["wide"] == "root_w"
    assert stats["full_changed_rows"] >= 28
    assert counter("decision.tpu.full_changed_rows") == (
        gained + stats["full_changed_rows"])
    assert counter("decision.tpu.wide_epochs") == wide + 1
    stamped = counters.get_statistics(
        "decision.tpu.full_changed_rows", windows=(60,)
    )["decision.tpu.full_changed_rows"]["60"]
    assert stamped["sum"] >= stats["full_changed_rows"]

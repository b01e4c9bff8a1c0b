"""A switch drained by its overload bit (benchmark configuration
fabric10k_pfx_drain): `AdjacencyDatabase.is_overloaded` set on one node,
every link up, no transit through it, routes to it kept.

A small three-tier fabric (4 pods of 4 fabric and 6 rack switches, 4 planes
of 2 spine switches: 48 switches, 3 prefixes each) through the Decision
actor with the TPU solver, each table against the benchmark's plain
reference of no-transit nodes (benchmark/references/node_drain.py), route
for route, the events made by the benchmark's LSDB model
(benchmark/lsdbs/node_drain.py): a fabric switch of another pod, one of the
vantage's own pod (a neighbour lane whose source is drained), a spine
switch and a rack switch (no route changes; the rack switch's prefixes kept
by the all-drained fallback), two switches in one epoch, a drain and a link
flap in one epoch; every drain takes the incremental solve with no full
pull and puts the packed announcer matrix once, and the give-back returns
the table of before, entry for entry. The reference itself against the CPU
oracle over seeded sets of drained switches, what it refuses, that
reference.py with the drain ignored differs on exactly the routes behind
the switch, the model's operations, and the span and counters a drain
leaves (`tpu.sync.pack`, `decision.tpu.overload_flips`,
`.mbuf_put_bytes`, `.drained_nodes`) with the benchmark's readers of them.
"""

import asyncio
import os
import random
import sys
from dataclasses import replace

import pytest

from openr_tpu.config import DecisionConfig
from openr_tpu.decision.spf_solver import SpfSolver
from openr_tpu.models import topologies
from openr_tpu.runtime.counters import counters
from openr_tpu.types import InitializationEvent, PrefixDatabase, PrefixEntry
from tests.conftest import run_async
from tests.test_decision import DecisionHarness

BENCH = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmark"
)
FABRIC = {"pods": 4, "planes": 4, "ssws_per_plane": 2, "rsws_per_pod": 6}
PER_NODE = 3
NODES = 4 * (4 + 6) + 4 * 2
ME = "pod000-rsw00"
AREA = "0"
CONFIG = {
    "generator": {
        "call": "fabric", "args": [],
        "kwargs": {**FABRIC, "prefixes_per_node": PER_NODE},
    },
    "vantage": ME, "solver_backend": "tpu",
    "decision_config": {"enable_lfa": True},
    "lsdb_module": "node_drain", "reference_module": "node_drain",
}


@pytest.fixture(scope="module")
def bench():
    """The benchmark's own modules, found the way run.py finds them."""
    sys.path.insert(0, BENCH)
    try:
        import files
        import reference

        class Bench:
            model = files.lsdb_module(CONFIG)
            ref = files.reference_module(CONFIG)
            plain = reference

        yield Bench
    finally:
        sys.path.remove(BENCH)


def counter(key: str) -> float:
    return counters.get_counter(key) or 0


class Served:
    """Decision with the TPU solver over the benchmark's LSDB model: each
    `event` is one publication, as the benchmark's harness sends it, and
    every table is held to the reference on the model as it stands and as
    `replay` gives it."""

    def __init__(self, bench, harness: DecisionHarness):
        self.bench, self.h = bench, harness
        self.lsdb = bench.model.build(CONFIG)
        self.decision = harness.decision

    async def boot(self) -> None:
        for kvs in self.lsdb.key_vals().values():
            self.h.publish(*kvs.items())
        self.h.synced()
        first = await self.h.next_route_update()
        assert len(first.unicast_routes_to_update) == (NODES - 1) * PER_NODE
        self.check("the first table")

    @property
    def area_dev(self):
        return self.decision.solver._area_dev[AREA]

    def table(self) -> dict:
        return self.bench.ref.programmed(
            {"unicast": dict(self.decision.route_db.unicast_routes)}
        )

    def check(self, ctx: str) -> dict:
        ref = self.bench.ref
        want = ref.routes(self.lsdb, ME, CONFIG)
        then = self.lsdb.replay(len(self.lsdb.log))
        assert ref.routes(then, ME, CONFIG) == want, ctx
        assert then.drained == self.lsdb.drained, ctx
        got = self.table()
        check = ref.compare(got, want)
        assert check["routes_compared"] == (NODES - 1) * PER_NODE, ctx
        assert (check["missing"], check["extra"], check["differing"]) == (
            0, 0, 0), (ctx, check)
        assert check["drained"] == sorted(self.lsdb.drained), ctx
        return got

    async def event(self, ops: list, ctx: str, routes_change: bool = True):
        """-> (the route update or None, the solver's device stats)."""
        epoch = self.decision._solve_epoch
        changed = self.lsdb.apply(ops)
        for kvs in self.lsdb.publication(changed).values():
            self.h.publish(*kvs.items())
        update = None
        if routes_change:
            update = await self.h.next_route_update()
        else:
            for _ in range(500):
                if self.decision._solve_epoch > epoch:
                    break
                await asyncio.sleep(0.01)
            await asyncio.sleep(0.05)
            while self.h.routes_reader.size():  # no update was sent
                ok, item = self.h.routes_reader.try_get()
                assert isinstance(item, InitializationEvent), (ctx, item)
        assert self.decision._solve_epoch == epoch + 1, ctx
        self.check(ctx)
        return update, dict(self.decision.solver.last_device_stats)


def served(fn):
    """An async test given a booted `Served`."""

    @run_async
    async def wrapper(bench, *args, **kwargs):
        config = DecisionConfig(
            debounce_min_ms=5, debounce_max_ms=20, enable_lfa=True
        )
        async with DecisionHarness(ME, backend="tpu", config=config) as h:
            s = Served(bench, h)
            await s.boot()
            await fn(s, *args, **kwargs)

    wrapper.__name__ = fn.__name__
    wrapper.__doc__ = fn.__doc__
    return wrapper


def behind(table: dict, pod: str) -> set:
    """The prefixes of `pod`'s rack switches, by the generator's own
    databases."""
    _, prefix_dbs = topologies.fabric(**FABRIC, prefixes_per_node=PER_NODE)
    return {
        e.prefix for db in prefix_dbs for e in db.prefix_entries
        if db.this_node_name.startswith(f"{pod}-rsw")
    } & table.keys()


# what is drained -> (do the vantage's routes change, next hops lost on
# the routes that do)
CASES = {
    "a_fabric_switch_of_another_pod": (["pod002-fsw01"], True),
    "a_fabric_switch_of_the_vantages_pod": (["pod000-fsw02"], True),
    "a_spine_switch": (["zspine01-ssw00"], False),
    "a_rack_switch_with_prefixes": (["pod003-rsw04"], False),
    "two_switches_in_one_epoch": (["pod001-fsw00", "pod003-fsw03"], True),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_a_drain_is_held_to_the_reference_and_given_back(bench, case):
    nodes, routes_change = CASES[case]

    @served
    async def drive(s: Served):
        before = s.table()
        failovers = counter("decision.solver.failovers")
        puts = s.area_dev.mbuf_puts
        flips = counter("decision.tpu.overload_flips")
        update, stats = await s.event(
            [("drain", n) for n in nodes], case, routes_change
        )
        # the incremental solve over the resident plane, the delta pull,
        # and the packed announcer matrix put once, whole
        assert stats.get("incremental") and not stats["full_pull"], stats
        # an uplink of the vantage's drained grows a cone past the limit
        # (a quarter of the lanes' cells): that program seeds itself cold
        assert stats["fell_back"] == (
            case == "a_fabric_switch_of_the_vantages_pod"), stats
        assert s.area_dev.mbuf_puts == puts + 1
        assert counter("decision.tpu.overload_flips") == flips + len(nodes)
        assert counter("decision.tpu.drained_nodes") == len(nodes)
        out = s.table()
        moved = {p for p in before if before[p] != out[p]}
        if case == "a_fabric_switch_of_another_pod":
            # the pod's rack switches lose the drained plane's path; the
            # switch's own prefixes keep their one
            assert moved == behind(out, "pod002")
            assert len(moved) == FABRIC["rsws_per_pod"] * PER_NODE
            assert all(len(out[p][1]) == 3 and len(before[p][1]) == 4
                       and out[p][0] == before[p][0] == 4 for p in moved)
            assert set(update.unicast_routes_to_update) == moved
            kept = [p for p, r in out.items()
                    if r[0] == 3 and {h[0] for h in r[1]} == {"pod000-fsw01"}]
            assert len(kept) == (FABRIC["pods"] - 1) * PER_NODE
        elif case == "a_fabric_switch_of_the_vantages_pod":
            # a next hop to its own prefixes alone
            via = [p for p, r in out.items()
                   if "pod000-fsw02" in {h[0] for h in r[1]}]
            assert len(via) == PER_NODE and all(out[p][0] == 1 for p in via)
            assert len(moved) > 20 * PER_NODE
        elif case == "two_switches_in_one_epoch":
            assert moved == behind(out, "pod001") | behind(out, "pod003")
        else:
            assert not moved and update is None
        update, stats = await s.event(
            [("undrain", n) for n in nodes], f"{case}, given back",
            routes_change,
        )
        assert stats.get("incremental") and not stats["fell_back"], stats
        assert not stats["full_pull"], stats
        assert s.area_dev.mbuf_puts == puts + 2
        assert counter("decision.tpu.drained_nodes") == 0
        assert s.table() == before  # entry for entry
        assert counter("decision.solver.failovers") == failovers

    drive(bench)


@served
async def test_a_drain_and_a_link_flap_in_one_epoch(s: Served):
    before = s.table()
    puts = s.area_dev.mbuf_puts
    _, stats = await s.event(
        [("drain", "pod002-fsw01"), ("down", "pod003-rsw02", "pod003-fsw00")],
        "a drain and a link down",
    )
    assert stats.get("incremental") and not stats["full_pull"], stats
    assert s.area_dev.mbuf_puts == puts + 1
    out = s.table()
    moved = {p for p in before if before[p] != out[p]}
    assert len(moved) == (FABRIC["rsws_per_pod"] + 1) * PER_NODE
    # the link comes back while the switch is still out, then the switch
    _, stats = await s.event(
        [("up", "pod003-rsw02", "pod003-fsw00")], "the link up, switch out"
    )
    assert stats.get("incremental") and not stats["full_pull"], stats
    assert s.area_dev.mbuf_puts == puts + 1  # no bit moved: no put
    assert s.lsdb.drained == {"pod002-fsw01"}
    await s.event([("undrain", "pod002-fsw01")], "the switch given back")
    assert s.table() == before


@served
async def test_a_link_of_a_drained_switch_flaps(s: Served):
    """The model keeps the bit on a database a link operation rebuilds."""
    before = s.table()
    await s.event([("drain", "pod001-fsw03")], "drained")
    await s.event(
        [("down", "pod001-fsw03", "pod001-rsw00")], "its link down",
        routes_change=False,  # nothing went through it already
    )
    i = s.lsdb.index["pod001-fsw03"]
    assert s.lsdb.adj_dbs[i].is_overloaded
    assert len(s.lsdb.adj_dbs[i].adjacencies) == 2 + 6 - 1
    await s.event([("undrain", "pod001-fsw03")], "given back, link down")
    await s.event([("up", "pod001-fsw03", "pod001-rsw00")], "link up")
    assert s.table() == before


@served
async def test_the_drain_ignored_differs_on_the_routes_behind_the_switch(
    s: Served,
):
    """reference.py on the same LSDB with the bit cleared (its graph: the
    drain ignored) fails the comparison on the rack switches' routes behind
    the drained switch, 6 x 3 here and 48 x 32 at full size, and on no
    other: the comparison can see the mechanism."""
    await s.event([("drain", "pod002-fsw01")], "drained")
    plain = s.bench.plain
    with pytest.raises(plain.Unsupported, match="is drained"):
        plain.routes(s.lsdb.adj_dbs, s.lsdb.prefix_dbs, ME, True)
    ignored = plain.routes(
        [replace(db, is_overloaded=False) for db in s.lsdb.adj_dbs],
        s.lsdb.prefix_dbs, ME, True,
    )
    got = s.table()
    check = s.bench.ref.compare(got, ignored)
    assert (check["missing"], check["extra"]) == (0, 0)
    assert check["differing"] == FABRIC["rsws_per_pod"] * PER_NODE
    assert {p for p in got if got[p] != ignored[p]} == behind(got, "pod002")
    # and with the switch given back the two references agree
    await s.event([("undrain", "pod002-fsw01")], "given back")
    assert s.bench.ref.routes(s.lsdb, ME, CONFIG) == plain.routes(
        s.lsdb.adj_dbs, s.lsdb.prefix_dbs, ME, True
    )


@served
async def test_a_drain_leaves_its_span_and_counters(s: Served):
    solver = s.decision.solver

    def spans() -> dict:
        return {name: attrs for name, _, _, _, attrs
                in solver.last_timing["spans"]}

    put_bytes = counter("decision.tpu.mbuf_put_bytes")
    flips = counter("decision.tpu.overload_flips")
    await s.event([("drain", "pod002-fsw01")], "drained")
    pack = spans()["tpu.sync.pack"]
    p_cap, a_cap = s.area_dev.matrix.ann_node.shape
    assert pack.items() >= {
        "cells": p_cap * a_cap, "flags_changed": PER_NODE, "put": True,
        "bytes": 6 * p_cap * a_cap * 4, "overload_flips": 1,
    }.items()
    parent = {name: parent for name, parent, *_ in
              solver.last_timing["spans"]}
    assert parent["tpu.sync.pack"] == "tpu.sync"
    assert counter("decision.tpu.mbuf_put_bytes") == put_bytes + pack["bytes"]
    assert counter("decision.tpu.overload_flips") == flips + 1
    assert counter("decision.tpu.drained_nodes") == 1
    # a link event while the switch is out packs nothing
    await s.event([("down", "pod003-rsw02", "pod003-fsw00")], "a link down")
    assert "tpu.sync.pack" not in spans()
    assert counter("decision.tpu.mbuf_put_bytes") == put_bytes + pack["bytes"]
    assert counter("decision.tpu.drained_nodes") == 1
    await s.event([("undrain", "pod002-fsw01")], "given back")
    assert spans()["tpu.sync.pack"]["flags_changed"] == PER_NODE
    assert counter("decision.tpu.overload_flips") == flips + 2
    assert counter("decision.tpu.drained_nodes") == 0
    # every addition is a stamped sample too: a window's gain can be read
    for key in ("decision.tpu.overload_flips", "decision.tpu.mbuf_put_bytes"):
        got = counters.get_statistics(key, windows=(3600,))[key]["3600"]
        assert got["sum"] >= 2, (key, got)


# -- the reference and the model on their own --------------------------------


@pytest.mark.parametrize("seed", [1, 2, 3, 4])
def test_the_reference_matches_the_oracle_with_drained_nodes(bench, seed):
    """Seeded sets of one to four drained switches of every tier, the
    vantage's own uplinks among them: the CPU oracle's table and the plain
    reference's, route for route, LFA on."""
    rng = random.Random(seed)
    adj_dbs, prefix_dbs = topologies.fabric(
        **FABRIC, prefixes_per_node=PER_NODE
    )
    names = [db.this_node_name for db in adj_dbs if db.this_node_name != ME]
    out = set(rng.sample(names, rng.randint(1, 4)))
    if seed % 2:
        out.add(f"pod000-fsw0{rng.randrange(4)}")
    adj_dbs = [
        replace(db, is_overloaded=db.this_node_name in out) for db in adj_dbs
    ]
    states, ps = topologies.build_states(adj_dbs, prefix_dbs)
    db = SpfSolver(ME, enable_lfa=True).build_route_db(ME, states, ps)
    want = bench.ref.routes_of(adj_dbs, prefix_dbs, ME, True)
    assert want.drained == tuple(sorted(out))
    check = bench.ref.compare(
        bench.ref.programmed({"unicast": dict(db.unicast_routes)}), want
    )
    assert (check["missing"], check["extra"], check["differing"]) == (
        0, 0, 0), check
    assert check["routes_compared"] == (NODES - 1) * PER_NODE


REFUSED = {
    "a_drained_adjacency": "held or drained adjacency",
    "a_soft_drain": "soft-drained",
    "a_second_advertiser": "two advertisers",
    "a_second_area": "more than one area",
    "a_drained_vantage": "vantage .* is drained",
}


@pytest.mark.parametrize("case", sorted(REFUSED))
def test_the_reference_refuses_what_the_deployment_does_not_use(bench, case):
    adj_dbs, prefix_dbs = topologies.fabric(
        **FABRIC, prefixes_per_node=PER_NODE
    )
    db = adj_dbs[5]
    if case == "a_drained_adjacency":
        adj_dbs[5] = replace(db, adjacencies=(
            replace(db.adjacencies[0], is_overloaded=True),
            *db.adjacencies[1:],
        ))
    elif case == "a_soft_drain":
        adj_dbs[5] = replace(db, node_metric_increment=10)
    elif case == "a_second_advertiser":
        entry = prefix_dbs[0].prefix_entries[0]
        prefix_dbs.append(PrefixDatabase(
            "pod003-rsw01", (PrefixEntry(prefix=entry.prefix),), AREA
        ))
    elif case == "a_second_area":
        prefix_dbs[7] = replace(prefix_dbs[7], area="1")
    else:
        i = next(k for k, d in enumerate(adj_dbs) if d.this_node_name == ME)
        adj_dbs[i] = replace(adj_dbs[i], is_overloaded=True)
    with pytest.raises(bench.ref.Unsupported, match=REFUSED[case]):
        bench.ref.routes_of(adj_dbs, prefix_dbs, ME, True)


def test_announcers_drops_the_drained_unless_all_are(bench):
    pick = bench.ref.announcers
    assert pick(["a", "b"], {"b"}) == ["a"]
    assert pick(["a", "b"], {"a", "b"}) == ["a", "b"]
    assert pick(["a"], {"a"}) == ["a"] and pick(["a"], set()) == ["a"]


def test_the_model_drains_one_key_and_replays(bench):
    from openr_tpu.serde import deserialize
    from openr_tpu.types import AdjacencyDatabase

    lsdb = bench.model.build(CONFIG)
    made = bench.model.build(CONFIG)
    node = "pod002-fsw01"
    changed = lsdb.apply([("drain", node)])
    assert changed == [node] and lsdb.drained == {node}
    (area, kvs), = lsdb.publication(changed).items()
    (key, value), = kvs.items()
    assert (area, key, value.version) == (AREA, f"adj:{node}", 2)
    db = deserialize(value.value, AdjacencyDatabase)
    base = made.adj_dbs[made.index[node]]
    assert db == replace(base, is_overloaded=True)
    assert len(db.adjacencies) == 2 + 6
    with pytest.raises(ValueError, match="is drained"):
        lsdb.apply([("drain", node)])
    with pytest.raises(ValueError, match="is not drained"):
        lsdb.apply([("undrain", "pod001-fsw00")])
    with pytest.raises(ValueError, match="not in the LSDB"):
        lsdb.apply([("drain", "pod009-fsw00")])
    del lsdb.log[1:]  # the three refused batches
    lsdb.apply([("metric", "pod001-rsw00", "pod001-fsw00", 5)])
    lsdb.apply([("drain", "zspine00-ssw01"), ("undrain", node)])
    assert lsdb.drained == {"zspine00-ssw01"}
    for batches, want in ((0, set()), (1, {node}), (2, {node}),
                          (3, {"zspine00-ssw01"})):
        then = lsdb.replay(batches)
        assert then.drained == want
        assert {db.this_node_name for db in then.adj_dbs
                if db.is_overloaded} == want
    assert lsdb.replay(3).adj_dbs == lsdb.adj_dbs
    assert lsdb.replay(0).key_vals() == made.key_vals()
    # the default model's operations are still understood, and nothing else
    with pytest.raises(ValueError, match="unknown link operation"):
        lsdb.apply([("isolate", "pod001-rsw00", "pod001-fsw00")])


def test_the_readers_read_the_counters_gain_or_nothing(bench, monkeypatch):
    """benchmark/layer_metrics/mbuf_put_mb_per_epoch.py and
    overload_flips_per_epoch.py: None with no window observed; else what
    the counter gained since the window's start over the window's epochs."""
    import time

    from openr_tpu.decision.tpu_solver import TpuSpfSolver

    sys.path.insert(0, BENCH)
    try:
        import files
        import loop_holds

        put, flips = (
            files.load_module(
                os.path.join(BENCH, "layer_metrics", f"{name}.py")
            ) for name in ("mbuf_put_mb_per_epoch", "overload_flips_per_epoch")
        )
    finally:
        sys.path.remove(BENCH)
    assert put.read({}) is None and flips.read({}) is None
    time.sleep(0.02)  # clear of what an earlier test added
    start = time.monotonic()
    monkeypatch.setattr(
        loop_holds, "window_bounds", lambda series: (start, start + 1.0)
    )
    time.sleep(0.01)
    TpuSpfSolver._count("decision.tpu.mbuf_put_bytes", 3_000_000)
    TpuSpfSolver._count("decision.tpu.mbuf_put_bytes", 5_000_000)
    TpuSpfSolver._count("decision.tpu.overload_flips", 2)
    series = {"window.epochs": [4]}
    assert put.read(series) == pytest.approx(2.0)
    assert flips.read(series) == pytest.approx(0.5)
    assert put.read({"window.epochs": []}) is None

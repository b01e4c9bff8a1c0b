"""Many prefixes a node (benchmark configuration fabric10k_pfx): the
device's [rows, advertisers] prefix plane larger than its [lanes, nodes]
distance plane, where every other test of the TPU solver, and every other
cell of the benchmark, carries one prefix a node and so as many rows as
node columns. A small three-tier fabric — 4 pods of 4 fabric and 6 rack
switches, 4 planes of 2 spine switches: 48 switches in 64 columns — at 1, 5
and 32 prefixes a switch: 64, 256 and 2,048 rows, so `p_cap` equal to,
above and 32 times `n_cap`, at fills (75, 93.75, 75 %) that are no power
of two. A row stage that reads the node plane by its own row index is
right at one prefix a node only by the accident of equal sizes and order.

Against the oracle: metric, next-hop set and loop-free alternate of every
route, on the full solve and on incremental ones, after seeded link downs
and ups; a weighted graph (`wan_rtt`) with several PrefixDatabases a node
so that routes with an alternate exist at many prefixes a node; the plain
reference of the benchmark against the same oracle; the three gauges that
say how large the prefix plane is; the benchmark's readers of them; the
generator's `prefixes_per_node`.
"""

import random

import pytest

from openr_tpu.decision.spf_solver import SpfSolver
from openr_tpu.decision.tpu_solver import TpuSpfSolver
from openr_tpu.models import topologies
from openr_tpu.ops.edgeplan import _next_pow2
from openr_tpu.runtime.counters import counters
from openr_tpu.types import PrefixDatabase, PrefixEntry, PrefixType
from tests.test_compact_rows import MODES, Recorder
from tests.test_incremental_spf import _Churn
from tests.test_tpu_solver import assert_rib_equal
from tests.test_wan_agg_lanes import _bench_module
from tests.test_wan_rtt_solver import _backups

FABRIC = {"pods": 4, "planes": 4, "ssws_per_plane": 2, "rsws_per_pod": 6}
NODES = 4 * (4 + 6) + 4 * 2
ME = "pod000-rsw00"
PER_NODE = [1, 5, 32]
SEEDS = [1, 2]
WAN = {"regions": 3, "cores": 2, "aggs": 4, "access": 20}
GAUGES = ("prefix_rows", "prefixes", "advertiser_cap")


def _fabric(k: int):
    adj_dbs, prefix_dbs = topologies.fabric(**FABRIC, prefixes_per_node=k)
    states, ps = topologies.build_states(adj_dbs, prefix_dbs)
    return adj_dbs, prefix_dbs, states, ps


def _wan(seed: int, k: int):
    """`wan_rtt`'s routers with k prefixes each: the generator's own
    loopback and k - 1 more, one PrefixDatabase a prefix."""
    adj_dbs, prefix_dbs = topologies.wan_rtt(**WAN, seed=seed)
    own = prefix_dbs[0].prefix_entries[0]
    more = [
        PrefixDatabase(
            this_node_name=db.this_node_name,
            prefix_entries=(PrefixEntry(
                prefix=f"fd01:{i:x}::{p:x}/128",
                type=PrefixType.LOOPBACK,
                forwarding_type=own.forwarding_type,
                forwarding_algorithm=own.forwarding_algorithm,
            ),),
            area=db.area,
        )
        for i, db in enumerate(prefix_dbs, 1) for p in range(1, k)
    ]
    prefix_dbs = prefix_dbs + more
    states, ps = topologies.build_states(adj_dbs, prefix_dbs)
    return adj_dbs, prefix_dbs, states, ps


def _rows(solver: TpuSpfSolver) -> tuple:
    stats = solver.last_device_stats
    return tuple(stats[key] for key in GAUGES)


def _drive(adj_dbs, states, ps, me: str, mode: str, rng, rows: tuple,
           per_node: int):
    """Full solve, then seeded link downs and ups (one of them the
    vantage's own), every table against the oracle's."""
    churn = _Churn(adj_dbs, states)
    cpu = SpfSolver(me, enable_lfa=True)
    tpu = TpuSpfSolver(
        me, enable_lfa=True, incremental_spf=(mode == "incremental")
    )
    assert tpu.small_graph_nodes == 0
    seen = {"incremental": 0, "changed": [], "backups": 0, "wide": 0}

    def solve(ctx: str):
        want = cpu.build_route_db(me, states, ps)
        got = tpu.build_route_db(me, states, ps)
        assert_rib_equal(want, got, f"{ctx} ({mode}, {per_node} a node)")
        assert len(want.unicast_routes) == (len(adj_dbs) - 1) * per_node, ctx
        assert _rows(tpu) == rows, ctx
        stats = tpu.last_device_stats
        warm = bool(stats.get("incremental") and not stats.get("fell_back"))
        seen["incremental"] += warm
        if warm:
            seen["changed"].append(stats["changed_rows"])
        seen["backups"] = max(seen["backups"], _backups(want))
        seen["wide"] = max(seen["wide"], max(
            len(r.nexthops) for r in want.unicast_routes.values()
        ))

    solve("the first, full solve")
    assert not tpu.last_device_stats.get("incremental")
    edges = [e for e in churn.edges() if me not in e]
    mine = [e for e in churn.edges() if me in e]
    for step, (u, v) in enumerate(rng.sample(edges, 4) + [mine[0]]):
        saved = churn.dbs[u], churn.dbs[v]
        churn.link_down(u, v)
        solve(f"down {step}: {u} - {v}")
        churn.link_up(u, v, *saved)
        solve(f"up {step}: {u} - {v}")
    # two links away in one epoch, given back in the other order
    (a, b), (c, d) = rng.sample(edges, 2)
    saved_ab = churn.dbs[a], churn.dbs[b]
    churn.link_down(a, b)
    saved_cd = churn.dbs[c], churn.dbs[d]
    churn.link_down(c, d)
    solve("two links down in one epoch")
    churn.link_up(a, b, *saved_ab)
    if {a, b} & {c, d}:
        # a shared switch's database came back whole: take c - d out again
        churn.link_down(c, d)
    solve("the first given back")
    churn.link_up(c, d, *saved_cd)
    if {a, b} & {c, d}:
        churn.link_up(a, b, *saved_ab)
    solve("the second given back")
    if mode == "incremental":
        assert seen["incremental"] >= 8, seen
    else:
        assert seen["incremental"] == 0, seen
    return seen


@pytest.mark.parametrize("mode", ["full", "incremental"])
@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("per_node", PER_NODE)
def test_many_prefixes_a_node_match_the_oracle(per_node, seed, mode):
    adj_dbs, _, states, ps = _fabric(per_node)
    assert len(adj_dbs) == NODES
    rows = (_next_pow2(NODES * per_node), NODES * per_node, 2)
    assert rows[0] == {1: 64, 5: 256, 32: 2048}[per_node]
    seen = _drive(
        adj_dbs, states, ps, ME, mode,
        random.Random(f"{seed}/{per_node}/rows"), rows, per_node,
    )
    # ECMP over the pod's four fabric switches; unit metrics: no alternate
    assert seen["wide"] == FABRIC["planes"] and seen["backups"] == 0, seen
    # a rack switch's uplink moves that switch's routes and no others:
    # rows change in whole switches' worth
    assert all(n % per_node == 0 for n in seen["changed"]), seen
    if mode == "incremental":
        assert any(seen["changed"]), seen


@pytest.mark.parametrize("mode", ["full", "incremental"])
@pytest.mark.parametrize("seed", SEEDS)
def test_many_prefixes_a_node_with_alternates_match_the_oracle(seed, mode):
    """RTT metrics, dual-homed access routers: most routes carry a
    loop-free alternate, at 5 prefixes a router (the LFA stage's
    [rows, advertisers, lanes] gather out of the node plane)."""
    per_node = 5
    adj_dbs, prefix_dbs, states, ps = _wan(seed, per_node)
    me = "r01-acc0000"
    n = len(adj_dbs)
    assert len(prefix_dbs) == n * per_node
    rows = (_next_pow2(n * per_node), n * per_node, 2)
    assert rows[0] > _next_pow2(n)
    seen = _drive(
        adj_dbs, states, ps, me, mode,
        random.Random(f"{seed}/wan/rows"), rows, per_node,
    )
    assert seen["backups"] * 2 >= (n - 1) * per_node, seen


@pytest.mark.parametrize("mode", ["full", "incremental"])
def test_32_a_node_ships_what_the_parent_shipped(monkeypatch, mode):
    """2,048 rows over 64 node columns, LFA on: every dispatch of seeded
    link downs and ups replayed through the parent commit's pipeline
    (tests/test_compact_rows.py) — the changed-rows payload bit for bit,
    32 rows or a multiple an event, found among 16 blocks of 128 rows;
    the cold pull's 1,504 rows equal where the host reads them and not
    built in a warm epoch."""
    per_node = 32
    adj_dbs, _, states, ps = _fabric(per_node)
    churn = _Churn(adj_dbs, states)
    cpu = SpfSolver(ME, enable_lfa=True)
    tpu = TpuSpfSolver(ME, enable_lfa=True, **MODES[mode])
    rec = Recorder(monkeypatch, tpu)

    def solve(ctx: str):
        want = cpu.build_route_db(ME, states, ps)
        assert_rib_equal(want, tpu.build_route_db(ME, states, ps), ctx)

    solve("the first solve")
    variant, want_full, count, cold = rec.epochs[0]
    assert variant.p_cap == 2048 and want_full == 1 and cold
    # against zeroed planes every row differs (no alternate reads -1), and
    # 2,048 is under the budget: it is want_full that asks for the table
    assert count == variant.p_cap < variant.budget
    rng = random.Random(f"{mode}/32/payload")
    for u, v in rng.sample([e for e in churn.edges() if ME not in e], 3):
        saved = churn.dbs[u], churn.dbs[v]
        churn.link_down(u, v)
        solve(f"{u} - {v} down")
        churn.link_up(u, v, *saved)
        solve(f"{u} - {v} up")
    warm = rec.epochs[1:]
    assert len(warm) == 6 and not any(cold for *_, cold in warm), warm
    assert all(count % per_node == 0 for _, _, count, _ in warm), warm
    assert any(count for _, _, count, _ in warm), warm


@pytest.mark.parametrize("graph", ["fabric5", "fabric32", "wan5"])
def test_the_plain_reference_matches_the_oracle_at_many_prefixes(graph):
    """benchmark/reference.py, the comparison that decides the benchmark's
    `correct`, where a node advertises several prefixes, each in a
    PrefixDatabase of its own."""
    reference = _bench_module("reference.py")
    if graph == "wan5":
        adj_dbs, prefix_dbs, states, ps = _wan(1, 5)
        me, per_node = "r01-acc0000", 5
    else:
        per_node = int(graph[len("fabric"):])
        adj_dbs, prefix_dbs, states, ps = _fabric(per_node)
        me = ME
    db = SpfSolver(me, enable_lfa=True).build_route_db(me, states, ps)
    got = reference.programmed(dict(db.unicast_routes))
    want = reference.routes(adj_dbs, prefix_dbs, me, True)
    check = reference.compare(got, want)
    assert (check["missing"], check["extra"], check["differing"]) == (
        0, 0, 0), check
    assert len(want) == (len(adj_dbs) - 1) * per_node
    backed = [p for p, route in want.items() if route[2]]
    assert bool(backed) == (graph == "wan5")
    # one route of a node's several gone, or with one next hop fewer, is seen
    pfx = sorted(want)[len(want) // 2]
    cost, hops, backup = got[pfx]
    less = {p: r for p, r in got.items() if p != pfx}
    assert reference.compare(less, want)["missing"] == 1
    if len(hops) > 1:
        fewer = frozenset(sorted(hops)[1:])
        assert reference.compare(
            {**got, pfx: (cost, fewer, backup)}, want
        )["differing"] == 1


@pytest.mark.parametrize("per_node", PER_NODE)
def test_the_row_gauges_say_the_prefix_plane(per_node):
    """decision.tpu.prefix_rows / prefixes / advertiser_cap,
    last_device_stats and the tpu.sync.plan and tpu.dispatch spans: the
    padded shape of the announcer matrix as the device holds it and the
    count of prefixes, set at the load and the same over incremental
    epochs (a link's change moves no row)."""
    adj_dbs, _, states, ps = _fabric(per_node)
    solver = TpuSpfSolver(ME, enable_lfa=True, incremental_spf=True)
    churn = _Churn(adj_dbs, states)

    def check(ctx: str):
        matrix = solver._area_dev["0"].matrix
        p_cap, a_cap = matrix.ann_node.shape
        want = (p_cap, NODES * per_node, a_cap)
        assert len(matrix.prefix_list) == NODES * per_node
        assert p_cap == _next_pow2(NODES * per_node) and a_cap == 2
        assert _rows(solver) == want, ctx
        for key, value in zip(GAUGES, want):
            assert counters.get_counter(f"decision.tpu.{key}") == value, ctx
        spans = {name: attrs for name, _, _, _, attrs
                 in solver.last_timing["spans"]}
        for key, value in zip(GAUGES, want):
            assert spans["tpu.sync.plan"][key] == value, ctx
        # beside the mirror's occupancy, not instead of it
        assert "residual_edges" in spans["tpu.sync.plan"], ctx
        assert spans["tpu.dispatch"]["rows"] == p_cap, ctx
        assert f"p={p_cap},a={a_cap}" in spans["tpu.dispatch"]["kernel"], ctx

    solver.build_route_db(ME, states, ps)
    check("the full solve")
    warm = 0
    for u, v in [e for e in churn.edges() if ME not in e][:3]:
        saved = churn.dbs[u], churn.dbs[v]
        churn.link_down(u, v)
        solver.build_route_db(ME, states, ps)
        check(f"{u} - {v} down")
        warm += bool(solver.last_device_stats.get("incremental"))
        churn.link_up(u, v, *saved)
        solver.build_route_db(ME, states, ps)
        check(f"{u} - {v} up")
        warm += bool(solver.last_device_stats.get("incremental"))
    assert warm == 6


def test_the_row_readers_read_the_gauges_or_nothing():
    """benchmark/layer_metrics/prefix_rows.py and prefix_row_fill.py: None
    with no window observed, None where the program has no gauge (the
    parent of the PR that added them), else rows and prefixes / rows."""
    readers = [
        _bench_module("layer_metrics", f"{name}.py")
        for name in ("prefix_rows", "prefix_row_fill")
    ]
    keys = ("decision.tpu.prefixes", "decision.tpu.prefix_rows")
    saved = {key: counters.get_counter(key) for key in keys}
    window = {"window.epochs": [3]}
    try:
        counters.set_counter(keys[0], 319232)
        counters.set_counter(keys[1], 524288)
        assert [r.read({}) for r in readers] == [None, None]
        assert [r.read({"window.epochs": []}) for r in readers] == [None] * 2
        assert readers[0].read(window) == 524288
        assert readers[1].read(window) == pytest.approx(60.888671875)
        with counters._lock:
            for key in keys:
                del counters._counters[key]
        assert [r.read(window) for r in readers] == [None, None]
        # rows without a count of prefixes: no share; a count alone: none
        counters.set_counter(keys[1], 524288)
        assert readers[1].read(window) is None
        with counters._lock:
            del counters._counters[keys[1]]
        counters.set_counter(keys[0], 319232)
        assert [r.read(window) for r in readers] == [None, None]
    finally:
        with counters._lock:
            for key, value in saved.items():
                counters._counters.pop(key, None)
                if value is not None:
                    counters._counters[key] = value


@pytest.mark.parametrize("per_node", [1, 5, 32])
def test_the_fabric_generator_gives_k_prefixes_a_node(per_node):
    """topologies.fabric(prefixes_per_node=k): k distinct prefixes a node,
    none shared between nodes, one PrefixDatabase a prefix, and the
    adjacency databases of k = 1."""
    adj_dbs, prefix_dbs = topologies.fabric(
        **FABRIC, prefixes_per_node=per_node
    )
    base_adj, base_pfx = topologies.fabric(**FABRIC)
    assert adj_dbs == base_adj
    assert len(base_pfx) == NODES
    by_node: dict = {}
    for db in prefix_dbs:
        assert len(db.prefix_entries) == 1
        by_node.setdefault(db.this_node_name, []).append(
            db.prefix_entries[0].prefix
        )
    assert sorted(by_node) == sorted(db.this_node_name for db in adj_dbs)
    assert all(len(set(p)) == per_node for p in by_node.values())
    every = [p for prefixes in by_node.values() for p in prefixes]
    assert len(set(every)) == len(every) == NODES * per_node

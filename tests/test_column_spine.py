"""Zero-copy columnar RIB→FIB spine parity (ISSUE 12 tentpole).

The packed column delta must be a drop-in for the per-route object
path at every stage it replaced:

  - fast_unicast_column_diff == the brute-force per-entry compare on
    randomized topologies through churn, overrides, and withdrawals
    (the legacy fast_unicast_diff + full compare stay in-tree as the
    oracle);
  - RouteColumnBatch decodes to exactly the entries the lazy RIB
    materializes (prefix set, metrics, next-hop groups);
  - the columnar dataplane programmer produces the same kernel op
    sequence, _metric record, and _stale make-before-break ledger as
    the per-route walk, including under injected failures;
  - ProvenanceLedger's bulk layer stamping answers get/pop exactly
    like the per-prefix RouteProvenance dict it replaced;
  - sync_fib_columns round-trips the packed arrays over the RPC
    boundary and reports partial failures as FibUpdateError.
"""

import dataclasses

import numpy as np
import pytest

from openr_tpu.decision.column_delta import (
    build_column_batch,
    fast_unicast_column_diff,
)
from openr_tpu.decision.columnar_rib import LazyUnicastRoutes
from openr_tpu.decision.rib import ProvenanceLedger, RouteProvenance
from openr_tpu.decision.tpu_solver import TpuSpfSolver
from openr_tpu.models import topologies
from openr_tpu.serde import to_plain
from openr_tpu.types import Adjacency, AdjacencyDatabase
from tests.conftest import run_async


def _flap(states, adj_dbs, node, metric, but=()):
    """Every link of `node` takes `metric`, but those to `but`."""
    victim = next(d for d in adj_dbs if d.this_node_name == node)
    states["0"].update_adjacency_database(
        AdjacencyDatabase(
            this_node_name=node,
            adjacencies=tuple(
                a if a.other_node_name in but
                else Adjacency(**{**a.__dict__, "metric": metric})
                for a in victim.adjacencies
            ),
            area="0",
        )
    )


def _withdraw(states, node):
    states["0"].update_adjacency_database(
        AdjacencyDatabase(this_node_name=node, adjacencies=(), area="0")
    )


def _counter(name):
    from openr_tpu.runtime.counters import counters

    return int(counters.get_counter(name) or 0)


# -- diff parity -----------------------------------------------------------


@pytest.mark.parametrize("seed,kw", [(3, {}), (21, {}),
                                     (42, {"enable_lfa": True})])
def test_column_diff_matches_brute_force_through_churn(seed, kw):
    """Property: for random topologies under metric churn, overrides,
    and node withdrawals, the packed column diff produces exactly the
    update/delete sets of the brute-force per-entry compare."""
    rng = np.random.default_rng(seed)
    adj_dbs, prefix_dbs = topologies.random_mesh(26, seed=seed)
    states, ps = topologies.build_states(adj_dbs, prefix_dbs)
    me = "node-0"
    tpu = TpuSpfSolver(me, **kw)
    db_old = tpu.build_route_db(me, states, ps)
    assert isinstance(db_old.unicast_routes, LazyUnicastRoutes)

    # cold: empty -> full table
    delta = fast_unicast_column_diff({}, db_old.unicast_routes)
    assert delta is not None and delta.full
    cold_mat = dict(db_old.unicast_routes)
    assert dict(delta.lazy_map()) == cold_mat
    assert delta.deletes == []

    engaged = 0
    for step in range(5):
        victim = f"node-{int(rng.integers(1, 26))}"
        if step == 3:
            _withdraw(states, victim)
        else:
            _flap(states, adj_dbs, victim, metric=int(rng.integers(2, 40)))
        db_new = tpu.build_route_db(me, states, ps)
        if step == 2:
            # host-side override (static-route merge shape): the diff
            # must route it through the entry-compare path
            pfx = next(iter(dict(db_new.unicast_routes)))
            db_new.unicast_routes[pfx] = dataclasses.replace(
                db_new.unicast_routes[pfx], igp_cost=777_777
            )
        upd = db_old.calculate_update(db_new)
        old_mat = dict(db_old.unicast_routes)
        new_mat = dict(db_new.unicast_routes)
        brute_update = {
            p: e for p, e in new_mat.items()
            if p not in old_mat or old_mat[p] != e
        }
        brute_dels = sorted(p for p in old_mat if p not in new_mat)
        ctx = f"seed={seed} step={step} victim={victim}"
        assert dict(upd.unicast_routes_to_update) == brute_update, ctx
        assert sorted(upd.unicast_routes_to_delete) == brute_dels, ctx
        if upd.columns is not None:
            engaged += 1
            assert len(upd.unicast_routes_to_update) == len(brute_update)
            assert set(upd.unicast_routes_to_update) == set(brute_update)
        db_old = db_new
    assert engaged >= 3, f"columnar diff engaged only {engaged}/5 steps"


@pytest.mark.parametrize("kw", [{}, {"enable_lfa": True}],
                         ids=["plain", "lfa"])
@pytest.mark.parametrize("seed", [3, 21, 42])
def test_full_result_on_a_warm_vantage_is_a_journaled_change(
    monkeypatch, seed, kw
):
    """Property: a full result that lands on a standing table (more rows
    changed than a delta pull holds: the budget is patched small before
    the solver is built) is one journal entry, and `calculate_update`
    over it is the columnar diff — update set, delete set and entries
    those of the brute-force per-entry compare, the legacy journal diff
    agreeing, and the epoch's RIB digest that of the same delta taken
    through the entry path."""
    import openr_tpu.decision.tpu_solver as ts
    from openr_tpu.decision.columnar_rib import fast_unicast_diff
    from openr_tpu.decision.rib import DecisionRouteUpdate
    from openr_tpu.decision.rib_digest import delta_digest

    monkeypatch.setattr(ts, "_DELTA_BUDGET", 2)
    rng = np.random.default_rng(seed)
    adj_dbs, prefix_dbs = topologies.random_mesh(26, seed=seed)
    states, ps = topologies.build_states(adj_dbs, prefix_dbs)
    me = "node-0"
    by_name = {d.this_node_name: d for d in adj_dbs}
    near = [a.other_node_name for a in by_name[me].adjacencies]
    # behind a neighbour, and no neighbour itself: it may leave with the
    # vantage's own links standing
    beyond = next(
        a.other_node_name
        for n in near for a in by_name[n].adjacencies
        if a.other_node_name not in (me, *near)
    )
    tpu = TpuSpfSolver(me, **kw)
    resets = _counter("decision.crib.full_resets")
    db_old = tpu.build_route_db(me, states, ps)
    crib = db_old.unicast_routes.segments[0].crib
    # the first RIB has no table to be a change of
    assert _counter("decision.crib.full_resets") == resets + 1
    assert crib.journal == [] and crib.journal_floor == crib.epoch
    floor = crib.journal_floor

    journaled = 0
    for step in range(6):
        # a neighbour's other links: the routes through it move, the
        # vantage's own links stand and so does its crib
        victim = near[step % len(near)]
        if step == 4:
            victim = beyond
            _withdraw(states, victim)
        else:
            _flap(
                states, adj_dbs, victim, int(rng.integers(2, 40)), but=(me,)
            )
        before = {
            name: _counter(f"decision.crib.{name}")
            for name in ("full_journaled", "full_resets")
        }
        db_new = tpu.build_route_db(me, states, ps)
        stats = tpu.last_device_stats
        assert db_new.unicast_routes.segments[0].crib is crib
        if step == 2:
            # a host-touched key beside the full result: it takes the
            # entry path, the rows beside it stay in columns
            pfx = next(iter(dict(db_new.unicast_routes)))
            db_new.unicast_routes[pfx] = dataclasses.replace(
                db_new.unicast_routes[pfx], igp_cost=777_777
            )
        upd = db_old.calculate_update(db_new)
        old_mat = dict(db_old.unicast_routes)
        new_mat = dict(db_new.unicast_routes)
        brute_update = {
            p: e for p, e in new_mat.items()
            if p not in old_mat or old_mat[p] != e
        }
        brute_dels = sorted(p for p in old_mat if p not in new_mat)
        ctx = f"seed={seed} step={step} victim={victim}"
        assert upd.columns is not None and not upd.columns.full, ctx
        assert upd.fast_diff, ctx
        assert dict(upd.unicast_routes_to_update) == brute_update, ctx
        assert sorted(upd.unicast_routes_to_delete) == brute_dels, ctx
        assert fast_unicast_diff(
            db_old.unicast_routes, db_new.unicast_routes
        ) == (brute_update, brute_dels), ctx
        assert delta_digest(upd) == delta_digest(DecisionRouteUpdate(
            unicast_routes_to_update=brute_update,
            unicast_routes_to_delete=brute_dels,
        )), ctx
        gained = {
            name: _counter(f"decision.crib.{name}") - n
            for name, n in before.items()
        }
        if stats["full_pull"]:
            journaled += 1
            assert gained == {"full_journaled": 1, "full_resets": 0}, ctx
            assert stats["changed_rows"] > 2, ctx
            # the rows journaled: those the device counted, but any
            # that is a route on neither side
            assert 2 < stats["full_changed_rows"] <= stats["changed_rows"]
            j_epoch, j_rows, j_exact = crib.journal[-1]
            assert j_epoch == crib.epoch and j_exact, ctx
            assert len(j_rows) == stats["full_changed_rows"], ctx
        else:
            assert gained == {"full_journaled": 0, "full_resets": 0}, ctx
        assert crib.journal_floor == floor, ctx
        db_old = db_new
    assert journaled >= 3, f"a full result landed in {journaled}/6 steps"


def test_column_diff_snapshot_isolated_from_later_churn():
    """The new_mapping a delta carries must keep answering with its own
    generation even after the solver patches the live columns (Fib
    holds it as programmed-state across later solves)."""
    adj_dbs, prefix_dbs = topologies.random_mesh(22, seed=11)
    states, ps = topologies.build_states(adj_dbs, prefix_dbs)
    me = "node-0"
    tpu = TpuSpfSolver(me)
    db1 = tpu.build_route_db(me, states, ps)
    delta = fast_unicast_column_diff({}, db1.unicast_routes)
    snap = delta.new_mapping
    before = dict(snap)
    _flap(states, adj_dbs, "node-3", metric=37)
    tpu.build_route_db(me, states, ps)
    assert dict(snap) == before


# -- the key index stands still across warm epochs -------------------------


def test_warm_epochs_build_no_key_index_and_only_changed_entries():
    """After the first warm epoch, further apply_rows + calculate_update
    + RouteState.update + Fib's read of its dirty set build no O(rows)
    key structure (decision.crib.key_index_builds stands still) and only
    the changed routes' entries (decision.rib.entries_built)."""
    from openr_tpu.fib.fib import RouteState

    adj_dbs, prefix_dbs = topologies.grid(7, node_labels=False)
    states, ps = topologies.build_states(adj_dbs, prefix_dbs)
    me = "node-3-3"
    tpu = TpuSpfSolver(me)
    db = tpu.build_route_db(me, states, ps)
    rs = RouteState()
    empty = type(db)()
    rs.update(empty.calculate_update(db))
    changed_epochs, builds = 0, None
    victims = [f"node-3-{c}" for c in (0, 1, 2, 4, 5, 6)] + ["node-1-3"]
    for step, victim in enumerate(victims):
        # a node on the vantage's own row: routes behind it change
        _flap(states, adj_dbs, victim, metric=3 + step)
        crib = db.unicast_routes.segments[0].crib
        floor = crib.journal_floor
        new_db = tpu.build_route_db(me, states, ps)
        assert crib.journal_floor == floor  # apply_rows, not a full reset
        entries = _counter("decision.rib.entries_built")
        upd = db.calculate_update(new_db)
        assert upd.columns is not None and not upd.columns.full
        rs.update(upd)
        dirty = dict.fromkeys(upd.unicast_routes_to_update, 0.0)
        dirty.update(dict.fromkeys(upd.unicast_routes_to_delete, 0.0))
        now = 1.0
        # Fib._program_dirty_routes' read of the table, as written there
        due = [p for p, ts in dirty.items() if ts <= now]
        due_routes = rs.unicast_routes_of(due)
        add_prefixes = [p for p, e in zip(due, due_routes) if e is not None]
        del_prefixes = [p for p, e in zip(due, due_routes) if e is None]
        assert [p in rs.unicast_routes for p in due] == [
            e is not None for e in due_routes]
        assert due_routes == [rs.unicast_route_of(p) for p in due]
        assert sorted(del_prefixes) == sorted(upd.unicast_routes_to_delete)
        assert len(rs.unicast_routes) == int(crib.cols.ok.sum())
        assert (
            _counter("decision.rib.entries_built") - entries
            == len(add_prefixes)
        ), step
        if builds is None:
            # the first warm epoch that changes a route may build the
            # matrix's index; from here on nothing O(rows) may be built
            if add_prefixes:
                builds = _counter("decision.crib.key_index_builds")
        else:
            changed_epochs += bool(add_prefixes)
            assert (
                _counter("decision.crib.key_index_builds") == builds
            ), step
        db = new_db
    assert changed_epochs >= 3, changed_epochs
    assert builds >= 1  # the counter does fire: the index was built once


@pytest.mark.parametrize("distinct", [False, True],
                         ids=["one_value", "every_row_distinct"])
def test_entry_groups_count_what_the_rows_columns_tell_apart(distinct):
    """decision.rib.entry_groups beside decision.rib.entries_built: N
    leaves behind one hub, their N routes moved by one event of the
    vantage's own link. Leaves at one distance read alike (one group),
    leaves at N distances do not (N groups); N entries are built either
    way, and the epoch's tpu.mat span and last_device_stats carry both
    counts."""
    from openr_tpu.types import PrefixForwardingAlgorithm

    n = 12
    me, hub = "node-me", "node-hub"
    leaves = [f"node-leaf{i:02d}" for i in range(n)]
    adj = topologies._adj
    nodes = {me: [adj(me, hub)], hub: [adj(hub, me)]}
    for i, leaf in enumerate(leaves):
        far = 1 + i if distinct else 1
        nodes[hub].append(adj(hub, leaf, metric=far))
        nodes[leaf] = [adj(leaf, hub, metric=far)]
    adj_dbs, prefix_dbs = topologies._mk_dbs(
        nodes, "0", PrefixForwardingAlgorithm.SP_ECMP, False)
    prefix_dbs = [db for db in prefix_dbs if db.this_node_name in leaves]
    states, ps = topologies.build_states(adj_dbs, prefix_dbs)
    tpu = TpuSpfSolver(me)
    db = tpu.build_route_db(me, states, ps)
    built, groups = (_counter("decision.rib.entries_built"),
                     _counter("decision.rib.entry_groups"))
    assert len(dict(db.unicast_routes)) == n  # the table, materialized
    want_groups = n if distinct else 1
    assert _counter("decision.rib.entries_built") - built == n
    assert _counter("decision.rib.entry_groups") - groups == want_groups
    for step, metric in enumerate((9, 4)):
        built, groups = (_counter("decision.rib.entries_built"),
                         _counter("decision.rib.entry_groups"))
        _flap(states, adj_dbs, me, metric)
        new_db = tpu.build_route_db(me, states, ps)
        assert _counter("decision.rib.entries_built") - built == n, step
        assert (
            _counter("decision.rib.entry_groups") - groups == want_groups
        ), step
        mat = {name: attrs for name, _, _, _, attrs
               in tpu.last_timing["spans"]}["tpu.mat"]
        assert mat["entries_built"] == n
        assert mat["entry_groups"] == want_groups
        stats = tpu.last_device_stats
        assert (stats["entries_built"], stats["entry_groups"]) == (
            n, want_groups)
        assert {e.igp_cost for e in new_db.unicast_routes.values()} == (
            {metric + 1 + i for i in range(n)} if distinct else {metric + 1}
        )
    # an epoch that moves no route builds none, and its span says nothing
    tpu.build_route_db(me, states, ps)
    mat = {name: attrs for name, _, _, _, attrs
           in tpu.last_timing["spans"]}["tpu.mat"]
    assert "entries_built" not in mat and "entry_groups" not in mat


def test_rehearsed_benchmark_stamps_key_index_builds_on_rib_diff(capsys):
    """In a rehearsal of the benchmark's flap cell (benchmark/rehearsal,
    CPU) every decision.rib_diff span carries `key_index_builds`, and
    the value no longer moves once the warm-up is over."""
    import os
    import sys

    from openr_tpu.runtime.counters import counters
    from openr_tpu.runtime.tracing import tracer

    # the run's no-hiding conditions read process-wide counters: a test
    # file that drove a failover earlier in this worker (test_faults.py)
    # must not make this run read as one
    for key in ("decision.solver.failovers", "decision.solver.degraded"):
        counters.set_counter(key, 0)
    bench = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "benchmark",
    )
    sys.path.insert(0, bench)
    try:
        import run

        tracer.clear()
        assert run.main([
            "--workload", "grid12.flap", "--seed", "7", "--seconds", "2",
            "--trace", "0", "--rehearse",
            "--root", os.path.join(bench, "rehearsal"),
        ]) == 0
    finally:
        sys.path.remove(bench)
    assert '"correct": true' in capsys.readouterr().out.splitlines()[-1]
    stamps = [
        sp["attributes"].get("key_index_builds")
        for tr in tracer.get_traces(limit=256)
        for sp in tr["spans"] if sp["name"] == "decision.rib_diff"
    ]
    assert len(stamps) >= 10 and None not in stamps, stamps
    # the window's epochs are the newest: the counter stood still
    assert stamps[-10] == stamps[-1] >= 1, stamps


# -- batch decode parity ---------------------------------------------------


def test_column_batch_matches_materialized_entries():
    """RouteColumnBatch must decode to exactly what the lazy RIB
    materializes: same prefixes, same metric, same next-hop group."""
    adj_dbs, prefix_dbs = topologies.random_mesh(24, seed=8)
    states, ps = topologies.build_states(adj_dbs, prefix_dbs)
    me = "node-0"
    db = TpuSpfSolver(me).build_route_db(me, states, ps)
    # one override rides the batch's object-path extra lane
    pfx = next(iter(dict(db.unicast_routes)))
    db.unicast_routes[pfx] = dataclasses.replace(
        db.unicast_routes[pfx], igp_cost=424_242
    )
    batch = build_column_batch(db.unicast_routes)
    assert batch is not None
    mat = dict(db.unicast_routes)
    decoded = batch.as_route_dicts()
    assert decoded.keys() == mat.keys()
    for p, entry in mat.items():
        d = decoded[p]
        assert d["igp_cost"] == entry.igp_cost, p
        want = sorted(
            (nh.address, nh.if_name, nh.weight, nh.metric)
            for nh in entry.nexthops
        )
        got = sorted(
            (nh["address"], nh["if_name"], nh["weight"], nh["metric"])
            for nh in d["nexthops"]
        )
        assert got == want, p
    # wire round trip is loss-free
    import json

    wired = batch.__class__.from_wire(
        json.loads(json.dumps(batch.to_wire()))
    )
    assert wired.as_route_dicts() == decoded


# -- dataplane programmer parity -------------------------------------------


class _ScriptedNetlink:
    """Records kernel mutations in order; fails specific
    (op, prefix, metric) calls with an errno."""

    def __init__(self, fail=()):
        self.ops: list[tuple[str, str, int]] = []
        self.fail = dict(fail)

    async def _do(self, op, r):
        self.ops.append((op, r.prefix, r.metric))
        eno = self.fail.get((op, r.prefix, r.metric))
        if eno is not None:
            import os

            raise OSError(eno, os.strerror(eno))

    async def add_route(self, r):
        await self._do("add", r)

    async def delete_route(self, r):
        await self._do("del", r)


def _scripted_dataplane(fake):
    from openr_tpu.platform.fib_handler import NetlinkDataplane

    dp = NetlinkDataplane.__new__(NetlinkDataplane)
    dp.table = 254
    dp.nl = fake
    dp._opened = True
    dp.mpls = {}
    dp._metric = {}
    dp._stale = {}
    dp.mpls_kernel = False
    return dp


def _per_prefix_ops(fake):
    seq: dict[str, list[tuple[str, int]]] = {}
    for op, p, m in fake.ops:
        seq.setdefault(p, []).append((op, m))
    return seq


@pytest.mark.parametrize("seed", [5, 19])
def test_columnar_programmer_matches_object_walk(seed):
    """Randomized churn + injected kernel failures: add_unicast_columns
    must leave the SAME _metric record, _stale make-before-break
    ledger, failed set, and per-prefix kernel op sequence as the
    per-route object walk driven with identical inputs."""
    import asyncio
    import errno

    rng = np.random.default_rng(seed)
    adj_dbs, prefix_dbs = topologies.random_mesh(22, seed=seed)
    states, ps = topologies.build_states(adj_dbs, prefix_dbs)
    me = "node-0"
    tpu = TpuSpfSolver(me)
    fake_obj = _ScriptedNetlink()
    fake_col = _ScriptedNetlink()
    dp_obj = _scripted_dataplane(fake_obj)
    dp_col = _scripted_dataplane(fake_col)

    async def step(db, fail):
        fake_obj.fail = dict(fail)
        fake_col.fail = dict(fail)
        routes = {p: to_plain(e) for p, e in dict(db.unicast_routes).items()}
        batch = build_column_batch(db.unicast_routes)
        assert batch is not None
        f_obj = await dp_obj.add_unicast(routes)
        f_col = await dp_col.add_unicast_columns(batch)
        return f_obj, f_col

    for i in range(4):
        if i:
            victim = f"node-{int(rng.integers(1, 22))}"
            _flap(states, adj_dbs, victim, metric=int(rng.integers(2, 40)))
        db = tpu.build_route_db(me, states, ps)
        if i == 2:
            # an override exercises the batch's extra (object) lane
            pfx = next(iter(dict(db.unicast_routes)))
            db.unicast_routes[pfx] = dataclasses.replace(
                db.unicast_routes[pfx], igp_cost=999_999
            )
        fail = {}
        if i >= 1:
            # fail a random add and a random old-metric cleanup delete
            mat = dict(db.unicast_routes)
            sample = sorted(mat)[: max(1, len(mat) // 8)]
            for p in sample[: len(sample) // 2]:
                fail[("add", p, mat[p].igp_cost)] = errno.ENOBUFS
            for p in sample[len(sample) // 2:]:
                old = dp_obj._metric.get(p)
                if old is not None and old != mat[p].igp_cost:
                    fail[("del", p, old)] = errno.EBUSY
        f_obj, f_col = asyncio.run(step(db, fail))
        ctx = f"seed={seed} step={i}"
        assert sorted(f_obj) == sorted(f_col), ctx
        assert dp_obj._metric == dp_col._metric, ctx
        assert dp_obj._stale == dp_col._stale, ctx
        assert _per_prefix_ops(fake_obj) == _per_prefix_ops(fake_col), ctx


# -- provenance ledger parity ----------------------------------------------


def test_provenance_ledger_matches_per_prefix_dict():
    """Randomized op sequence: the layered ledger must answer get/pop
    exactly like the plain per-prefix dict it replaced, including under
    layer folding (> _LAYER_MAX coexisting bulk stamps)."""
    rng = np.random.default_rng(0)
    prefixes = [f"10.0.{i}.0/24" for i in range(48)]
    ledger = ProvenanceLedger()
    mirror: dict[str, RouteProvenance] = {}
    ingest_tags: dict[str, tuple] = {}
    for step in range(1, 160):
        op = int(rng.integers(0, 10))
        if op < 3:  # explicit per-prefix stamp
            p = prefixes[int(rng.integers(0, len(prefixes)))]
            prov = RouteProvenance(
                kv_key=f"k{step}", originator=f"n{step}", area="0",
                solve_epoch=step, solver_kind="full", ts_ms=step,
            )
            ledger[p] = prov
            mirror[p] = prov
        elif op < 5:  # delete
            p = prefixes[int(rng.integers(0, len(prefixes)))]
            assert ledger.pop(p, None) == mirror.pop(p, None), step
        else:  # bulk layer (what a columnar build stamps)
            k = int(rng.integers(2, len(prefixes)))
            members = {
                prefixes[j]: None
                for j in rng.choice(len(prefixes), size=k, replace=False)
            }
            tags = {
                p: (f"t{step}", f"o{step}", "0")
                for p in list(members)[:: 2]
            }
            topo = (f"topo{step}", "origin", "0") if op >= 8 else None
            ingest = None
            if topo is None and ingest_tags:
                ingest = dict(ingest_tags)
            ledger.stamp_layer(
                dict(members), dict(tags), topo, ingest, step, "full", step
            )
            for p in members:
                tag = (
                    tags.get(p) or topo
                    or (ingest.get(p) if ingest else None)
                    or ("", "", "")
                )
                mirror[p] = RouteProvenance(
                    kv_key=tag[0], originator=tag[1], area=tag[2],
                    solve_epoch=step, solver_kind="full", ts_ms=step,
                )
            ingest_tags.update(tags)
        for p in prefixes:
            assert ledger.get(p) == mirror.get(p), (step, p)


# -- RPC boundary ----------------------------------------------------------


@run_async
async def test_sync_fib_columns_rpc_roundtrip():
    """Packed column sync across the real RPC boundary: the platform
    agent's table must match the batch, and per-prefix failures must
    come back as FibUpdateError (same contract as sync_fib)."""
    from openr_tpu.fib.fib_service import FibUpdateError
    from openr_tpu.platform.fib_handler import (
        FibPlatformServer,
        MemoryDataplane,
        RemoteFibService,
    )

    adj_dbs, prefix_dbs = topologies.random_mesh(18, seed=4)
    states, ps = topologies.build_states(adj_dbs, prefix_dbs)
    db = TpuSpfSolver("node-0").build_route_db("node-0", states, ps)
    batch = build_column_batch(db.unicast_routes)
    assert batch is not None

    dp = MemoryDataplane()
    server = FibPlatformServer(dp)
    port = await server.start()
    svc = RemoteFibService("127.0.0.1", port)
    try:
        assert svc.supports_columns
        await svc.sync_fib_columns(786, batch)
        table = await svc.get_route_table()
        want = batch.as_route_dicts()
        assert set(table["unicast"]) == set(want)
        some = next(iter(want))
        assert table["unicast"][some]["igp_cost"] == want[some]["igp_cost"]

        victim = sorted(want)[0]
        dp.fail_prefixes.add(victim)
        with pytest.raises(FibUpdateError) as ei:
            await svc.sync_fib_columns(786, batch)
        assert ei.value.failed_prefixes == [victim]
    finally:
        await svc.close()
        await server.stop()

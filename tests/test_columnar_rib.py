"""ColumnarRib / LazyUnicastRoutes properties (ISSUE 1 tentpole).

The columnar RIB keeps the solver's packed outputs as numpy columns and
builds RibUnicastEntry objects only at consumption boundaries. These
tests pin the load-bearing invariants:

  - materialized-lazily == built-eagerly, byte-identical, on randomized
    topologies through cold rebuilds AND steady-state delta patches
    (the CPU oracle builds every entry eagerly through an independent
    code path);
  - RibView snapshots are isolated from later churn (copy-on-write);
  - fast_unicast_diff (journal-bounded) == the brute-force full
    compare;
  - LazyUnicastRoutes honors MutableMapping semantics without forcing
    surprises.
"""

import numpy as np
import pytest

from openr_tpu.decision.columnar_rib import (
    LazyUnicastRoutes,
    fast_unicast_diff,
)
from openr_tpu.decision.prefix_state import PrefixState
from openr_tpu.decision.spf_solver import SpfSolver
from openr_tpu.decision.tpu_solver import TpuSpfSolver
from openr_tpu.models import topologies
from openr_tpu.types import Adjacency, AdjacencyDatabase


def _flap(states, adj_dbs, node, metric):
    victim = next(d for d in adj_dbs if d.this_node_name == node)
    states["0"].update_adjacency_database(
        AdjacencyDatabase(
            this_node_name=node,
            adjacencies=tuple(
                Adjacency(**{**a.__dict__, "metric": metric})
                for a in victim.adjacencies
            ),
            area="0",
        )
    )


def _assert_byte_identical(lazy_db, eager_db, context):
    mat = dict(lazy_db.unicast_routes)
    eager = eager_db.unicast_routes
    assert mat.keys() == eager.keys(), context
    for pfx, a in mat.items():
        b = eager[pfx]
        # dataclass __eq__ covers every field; repr pins the byte-level
        # rendering (field order, frozenset contents, defaults)
        assert a == b, f"{context}: {pfx}\n{a}\nvs\n{b}"
        assert sorted(map(repr, a.nexthops)) == sorted(map(repr, b.nexthops))
        assert a.__dict__.keys() == b.__dict__.keys(), (context, pfx)


@pytest.mark.parametrize("seed,kw", [(3, {}), (17, {}),
                                     (42, {"enable_lfa": True})])
def test_columnar_matches_eager_on_randomized_topologies(seed, kw):
    """Property: for random topologies, the lazily-materialized columnar
    RIB is byte-identical to the oracle's eagerly-built entries — cold,
    after a delta patch, and after a full invalidation."""
    rng = np.random.default_rng(seed)
    adj_dbs, prefix_dbs = topologies.random_mesh(28, seed=seed)
    states, ps = topologies.build_states(adj_dbs, prefix_dbs)
    me = "node-0"
    cpu = SpfSolver(me, **kw)
    tpu = TpuSpfSolver(me, **kw)
    tpu_db = tpu.build_route_db(me, states, ps)
    assert isinstance(tpu_db.unicast_routes, LazyUnicastRoutes)
    _assert_byte_identical(tpu_db, cpu.build_route_db(me, states, ps),
                           f"cold seed={seed}")
    # steady-state: a couple of metric flaps exercise the delta patch
    # path (apply_rows) and the journal
    for step in range(3):
        victim = f"node-{int(rng.integers(1, 28))}"
        _flap(states, adj_dbs, victim, metric=int(rng.integers(2, 30)))
        tpu_db = tpu.build_route_db(me, states, ps)
        _assert_byte_identical(
            tpu_db, cpu.build_route_db(me, states, ps),
            f"delta seed={seed} step={step} victim={victim}",
        )


def test_view_snapshots_isolated_from_churn():
    """A RibView snapshot taken before churn must keep answering with
    its own generation's routes (copy-on-write), even while the solver
    patches the live columns underneath."""
    adj_dbs, prefix_dbs = topologies.random_mesh(24, seed=7)
    states, ps = topologies.build_states(adj_dbs, prefix_dbs)
    me = "node-0"
    tpu = TpuSpfSolver(me)
    db1 = tpu.build_route_db(me, states, ps)
    before = dict(db1.unicast_routes)  # force + snapshot
    # drop node-3 entirely: its prefix route must disappear
    states["0"].update_adjacency_database(
        AdjacencyDatabase(this_node_name="node-3", adjacencies=(), area="0")
    )
    db2 = tpu.build_route_db(me, states, ps)
    after = dict(db2.unicast_routes)
    assert before != after, "churn did not change any route"
    # the old db still answers with the old generation
    assert dict(db1.unicast_routes) == before
    # and per-key lookups on the stale view agree with its snapshot
    for pfx in list(before)[:32]:
        assert db1.unicast_routes[pfx] == before[pfx]


def test_fast_unicast_diff_matches_brute_force():
    """The journal-bounded diff must produce exactly the same update set
    as the full per-entry compare."""
    adj_dbs, prefix_dbs = topologies.random_mesh(24, seed=5)
    states, ps = topologies.build_states(adj_dbs, prefix_dbs)
    me = "node-0"
    tpu = TpuSpfSolver(me)
    db1 = tpu.build_route_db(me, states, ps)
    _flap(states, adj_dbs, "node-4", metric=21)
    db2 = tpu.build_route_db(me, states, ps)
    res = fast_unicast_diff(db1.unicast_routes, db2.unicast_routes)
    assert res is not None, "fast path did not engage"
    to_update, dels = res
    old, new = dict(db1.unicast_routes), dict(db2.unicast_routes)
    brute_update = {
        p: e for p, e in new.items()
        if p not in old or old[p] != e
    }
    brute_dels = [p for p in old if p not in new]
    assert to_update == brute_update
    assert sorted(dels) == sorted(brute_dels)
    # the Fib-facing entry point reports the fast path
    upd = db1.calculate_update(db2)
    assert getattr(upd, "fast_diff", False)
    assert upd.unicast_routes_to_update == brute_update
    assert sorted(upd.unicast_routes_to_delete) == sorted(brute_dels)


def test_fast_diff_ineligible_pairs_fall_back():
    """Foreign mappings and unrelated lazies must return None (callers
    then run the full compare)."""
    adj_dbs, prefix_dbs = topologies.random_mesh(20, seed=9)
    states, ps = topologies.build_states(adj_dbs, prefix_dbs)
    me = "node-0"
    db = TpuSpfSolver(me).build_route_db(me, states, ps)
    assert fast_unicast_diff({}, db.unicast_routes) is None
    assert fast_unicast_diff(db.unicast_routes, {}) is None
    # two independent solvers => distinct cribs => ineligible
    other = TpuSpfSolver(me).build_route_db(me, states, ps)
    assert fast_unicast_diff(db.unicast_routes,
                             other.unicast_routes) is None


def test_lazy_mapping_semantics():
    """LazyUnicastRoutes is the dict DecisionRouteDb carries: overrides
    shadow views, deletes hide keys, equality is value-based."""
    adj_dbs, prefix_dbs = topologies.random_mesh(20, seed=13)
    states, ps = topologies.build_states(adj_dbs, prefix_dbs)
    me = "node-0"
    lazy = TpuSpfSolver(me).build_route_db(me, states, ps).unicast_routes
    plain = dict(lazy)
    assert len(lazy) == len(plain)
    assert set(lazy) == set(plain)
    assert lazy == plain and plain == dict(lazy)
    pfx = next(iter(plain))
    assert pfx in lazy and lazy[pfx] == plain[pfx]
    assert lazy.get("no-such-prefix/128") is None
    # override shadows the view without changing cardinality
    import dataclasses

    patched = dataclasses.replace(plain[pfx], igp_cost=999_999)
    lazy[pfx] = patched
    assert lazy[pfx] is patched and len(lazy) == len(plain)
    assert lazy != plain
    # delete hides the key
    del lazy[pfx]
    assert pfx not in lazy and len(lazy) == len(plain) - 1
    with pytest.raises(KeyError):
        del lazy["no-such-prefix/128"]
    # re-insert restores
    lazy[pfx] = plain[pfx]
    assert lazy == plain


# -- the key index: one per matrix, answers from the pinned ok mask --------


def _two_vantage_views(seed):
    """Two live tables over one topology from two vantages: their prefix
    sets overlap everywhere but at the vantages' own prefixes, and every
    shared prefix has different next hops — the shape of a multi-area
    table whose segments shadow each other."""
    adj_dbs, prefix_dbs = topologies.random_mesh(18, seed=seed)
    states, ps = topologies.build_states(adj_dbs, prefix_dbs)
    dbs = [
        TpuSpfSolver(me).build_route_db(me, states, ps)
        for me in ("node-0", "node-1")
    ]
    return [db.unicast_routes.segments[0] for db in dbs]


def _flip_ok(crib, rows, ok):
    """One apply_rows that rewrites `rows` with their own columns and
    the given ok bits (what a withdrawal / a re-announcement does)."""
    c = crib.cols
    rows = np.asarray(rows)
    crib.apply_rows(
        rows, c.met[rows].copy(), c.s3w[rows].copy(), c.nhw[rows].copy(),
        None if c.lfa_slot is None else c.lfa_slot[rows].copy(),
        None if c.lfa_metric is None else c.lfa_metric[rows].copy(),
        ok=np.asarray(ok, bool),
    )


@pytest.mark.parametrize("stale", [False, True], ids=["tip", "stale"])
@pytest.mark.parametrize("n_seg", [0, 1, 2])
@pytest.mark.parametrize("seed", [2, 11, 29])
def test_lazy_table_arithmetic_matches_materialized(seed, n_seg, stale):
    """len, truth, membership and per-key lookup of a LazyUnicastRoutes
    equal those of its materialized dict for seeded compositions of
    base, zero to two (overlapping) segments, overrides and deleted —
    over the tip and over a view a later apply_rows left stale."""
    from openr_tpu.decision.columnar_rib import _lookup

    rng = np.random.default_rng(seed * 7 + n_seg)
    both = _two_vantage_views(seed)
    views = both[:n_seg]
    everything = dict(both[1].all_routes())
    seg_keys = [k for v in views for k in v.prefixes()]
    if stale and views:
        # later churn on the crib: the table below must keep answering
        # from the generation its views pinned
        crib = views[0].crib
        ok_rows = views[0].key_rows()
        gone = rng.choice(ok_rows, size=4, replace=False)
        _flip_ok(crib, gone, [False] * 4)
        assert not views[0].current
    pool = sorted(everything)
    pick = lambda n: [pool[i] for i in rng.choice(len(pool), n, False)]
    absent = [f"fd00:dead::{i:x}/128" for i in range(6)]
    base = {k: everything[k] for k in pick(5)}
    base.update({k: everything[pool[0]] for k in absent[:2]})
    lz = LazyUnicastRoutes(base, views)
    lz.overrides = {k: everything[pool[1]] for k in pick(3) + absent[2:4]}
    lz.deleted = set(pick(6) + absent[3:5] + list(base)[:1])
    # an all-but-empty table too: everything deleted
    lz_empty = LazyUnicastRoutes(base, views)
    lz_empty.deleted = set(base) | set(seg_keys)

    for table in (lz, lz_empty):
        probe = sorted(set(pool) | set(absent) | set(seg_keys))
        n, truth = len(table), bool(table)
        member = {k: k in table for k in probe}
        found = {k: _lookup(table, k) for k in probe}
        mat = dict(table.snapshot().materialized())
        assert n == len(mat)
        assert truth == bool(mat)
        assert member == {k: k in mat for k in probe}
        assert found == {k: mat.get(k) for k in probe}
        # and read in bulk (Fib's dirty-route pass), over a fresh copy:
        # what it lacks it builds itself
        assert table.snapshot().lookup_many(probe) == [
            mat.get(k) for k in probe]
        assert set(table) == set(mat)  # the O(rows) dump agrees too
    assert not lz_empty and len(lz_empty) == 0


@pytest.mark.parametrize("seed", [4, 13])
def test_stale_view_answers_from_its_own_generation(seed):
    """After two apply_rows that flip ok both ways, each RibView answers
    has/get from the bundle it pinned — the matrix's prefix -> row index
    is shared, the ok mask is not."""
    from openr_tpu.runtime.counters import counters

    view0 = _two_vantage_views(seed)[0]
    crib = view0.crib
    plist = crib.matrix.prefix_list
    before = dict(view0.all_routes())
    r_a, r_b = (int(r) for r in view0.key_rows()[[1, 5]])
    p_a, p_b = plist[r_a], plist[r_b]
    assert view0.has(p_a)  # the matrix's index exists from here on
    builds = counters.get_counter("decision.crib.key_index_builds") or 0
    _flip_ok(crib, [r_a], [False])  # epoch 1: a leaves
    view1 = crib.view()
    _flip_ok(crib, [r_a, r_b], [True, False])  # epoch 2: a back, b leaves
    tip = crib.view()
    assert not view0.current and not view1.current and tip.current
    assert (view0.has(p_a), view1.has(p_a), tip.has(p_a)) == (
        True, False, True)
    assert (view0.has(p_b), view1.has(p_b), tip.has(p_b)) == (
        True, True, False)
    for view in (view0, view1, tip):
        for p, r in ((p_a, r_a), (p_b, r_b)):
            assert view._row_of(p) == (r if view.has(p) else None)
            got = view.get(p, bulk=False)
            assert got == (before[p] if view.has(p) else None)
    assert view1.n_rows() == tip.n_rows() == view0.n_rows() - 1
    assert not view0.has("fd00:dead::1/128")
    # three generations, one index: nothing O(rows) was built for them
    assert (
        counters.get_counter("decision.crib.key_index_builds") or 0
    ) == builds


# -- a full result: a journaled change where a table stands ----------------


def _counter(name):
    from openr_tpu.runtime.counters import counters

    return int(counters.get_counter(name) or 0)


def _land_full(crib, cols, lfa=True):
    """`cols`' ok rows as the device's compacted full buffer hands them."""
    rows = np.flatnonzero(cols.ok)
    with_lfa = lfa and cols.lfa_slot is not None
    return crib.set_full_packed(
        rows, cols.met[rows], cols.s3w[rows], cols.nhw[rows],
        cols.lfa_slot[rows] if with_lfa else None,
        cols.lfa_metric[rows] if with_lfa else None,
    )


def _perturbed(cols, rng, n):
    """A copy of `cols` with 3n rows moved: n withdrawn, n at another
    metric, n given the next hops (and alternate) of another row. Returns
    (copy, rows withdrawn, rows whose columns changed and still route)."""
    c = cols.copy()
    gone, dearer, swapped, donors = (
        rng.choice(np.flatnonzero(cols.ok), 4 * n, replace=False)
        .reshape(4, n)
    )
    c.ok[gone] = False
    for col in (c.met, c.s3w, c.nhw):
        col[gone] = 0
    c.met[dearer] += 7
    c.nhw[swapped] = cols.nhw[donors]
    if c.lfa_slot is not None:
        c.lfa_slot[gone] = -1
        c.lfa_metric[gone] = 0
        c.lfa_slot[swapped] = cols.lfa_slot[donors]
        c.lfa_metric[swapped] = cols.lfa_metric[donors]
    moved = np.concatenate([dearer, swapped])
    differs = (c.met[moved] != cols.met[moved]) | (
        c.nhw[moved] != cols.nhw[moved]).any(axis=1)
    if c.lfa_slot is not None:
        differs |= c.lfa_slot[moved] != cols.lfa_slot[moved]
        differs |= c.lfa_metric[moved] != cols.lfa_metric[moved]
    return c, np.sort(gone), np.sort(moved[differs])


def _fresh(crib):
    """The tip's bundle built row for row, beside every cache."""
    routes = {}
    crib._build_rows_into(crib.cols, crib.cols.key_rows(), routes)
    return routes


def _lfa_view(seed, lfa):
    adj_dbs, prefix_dbs = topologies.random_mesh(20, seed=seed)
    states, ps = topologies.build_states(adj_dbs, prefix_dbs)
    db = TpuSpfSolver("node-0", enable_lfa=lfa).build_route_db(
        "node-0", states, ps
    )
    return db.unicast_routes.segments[0]


@pytest.mark.parametrize("lfa", [False, True], ids=["plain", "lfa"])
@pytest.mark.parametrize("warm", [False, True], ids=["lazy", "materialized"])
@pytest.mark.parametrize("seed", [5, 23])
def test_full_result_over_a_standing_table(seed, warm, lfa):
    """The journal gets one exact entry of the rows that differ, floor
    and forced rows stand, a view taken before reads its own bundle, and
    the entry cache ends equal to a fresh build of the new bundle (all of
    it where the crib was materialized, what it holds where not) - with
    the changed rows built in one call."""
    from openr_tpu.decision.column_delta import fast_unicast_column_diff

    rng = np.random.default_rng(seed)
    view0 = _lfa_view(seed, lfa)
    crib = view0.crib
    assert (crib.cols.lfa_slot is not None) == lfa
    plist = crib.matrix.prefix_list
    before = dict(_fresh(crib))
    if warm:
        crib.materialize()
    else:
        for r in view0.key_rows()[:6].tolist():  # a partial cache
            crib.entry_for_row(r)
    old = LazyUnicastRoutes({}, [view0])
    new_cols, gone, moved = _perturbed(crib.cols, rng, 3)
    # an advertisement changed on a row the result leaves as it was
    still = next(
        int(r) for r in view0.key_rows()
        if r not in gone and r not in moved
    )
    crib.touch_rows([still])
    floor, epoch = crib.journal_floor, crib.epoch
    counts = (_counter("decision.crib.full_journaled"),
              _counter("decision.crib.full_resets"))
    built = []
    real_build = crib._build_rows_into
    crib._build_rows_into = lambda c, rows, out: (
        built.append(len(rows)), real_build(c, rows, out))[1]
    try:
        assert _land_full(crib, new_cols) == len(gone) + len(moved)
    finally:
        del crib._build_rows_into
    assert built == ([len(moved)] if warm else [])
    assert (_counter("decision.crib.full_journaled"),
            _counter("decision.crib.full_resets")) == (
                counts[0] + 1, counts[1])
    assert (crib.journal_floor, crib.epoch) == (floor, epoch + 1)
    j_epoch, j_rows, j_exact = crib.journal[-1]
    assert j_epoch == crib.epoch and j_exact
    assert j_rows.tolist() == sorted([*gone.tolist(), *moved.tolist()])
    assert crib.forced_rows_since(view0.epoch).tolist() == [still]
    # the view from before: its own generation, entry for entry
    assert not view0.current and view0.cols is not crib.cols
    assert dict(view0.all_routes()) == before
    # the cache: the new bundle's entries, no row of the old one left
    fresh = _fresh(crib)
    assert set(fresh) == set(before) - {plist[r] for r in gone.tolist()}
    if warm:
        assert crib.materialized and crib.routes == fresh
    else:
        assert not crib.materialized
        assert crib.routes == {p: fresh[p] for p in crib.routes}
        assert not {plist[r] for r in j_rows.tolist()} & set(crib.routes)
    # the diff over it stays in columns and sends the forced row too
    new = LazyUnicastRoutes({}, [crib.view()])
    delta = fast_unicast_column_diff(old, new)
    assert delta is not None and not delta.full
    assert sorted(delta.segments[0][1].tolist()) == sorted(
        [*moved.tolist(), still])
    assert sorted(delta.deletes) == sorted(plist[r] for r in gone.tolist())
    upd, dels = fast_unicast_diff(old, new)
    assert dict(delta.lazy_map()) == upd and delta.deletes == dels
    assert plist[still] in upd and upd[plist[still]] == before[plist[still]]


@pytest.mark.parametrize("case", ["first_rib", "lfa_vanishes", "lfa_appears"])
def test_full_result_with_no_table_to_compare_resets(case):
    """The first RIB of a crib, and a bundle whose LFA columns appear or
    vanish, reset the journal, the floor, the forced rows and the cache
    as every full result did."""
    resets = _counter("decision.crib.full_resets")
    journaled = _counter("decision.crib.full_journaled")
    view0 = _lfa_view(9, lfa=case != "lfa_appears")
    crib = view0.crib
    assert _counter("decision.crib.full_resets") == resets + 1
    if case != "first_rib":
        crib.materialize()
        cols = crib.cols.copy()
        if case == "lfa_appears":
            cols.lfa_slot = np.full(crib.p_n, -1, np.int32)
            cols.lfa_metric = np.zeros(crib.p_n, np.int32)
        crib.touch_rows(view0.key_rows()[:2])
        assert crib.journal and crib.forced
        assert _land_full(crib, cols, lfa=case == "lfa_appears") is None
        assert _counter("decision.crib.full_resets") == resets + 2
        assert (crib.cols.lfa_slot is not None) == (case == "lfa_appears")
    assert _counter("decision.crib.full_journaled") == journaled
    assert crib.journal == [] and crib.forced == []
    assert crib.journal_floor == crib.epoch
    assert crib.routes == {} and not crib.materialized
    assert not crib.covers(crib.epoch - 1)
    if case != "first_rib":
        assert not view0.current
        assert fast_unicast_diff(
            LazyUnicastRoutes({}, [view0]),
            LazyUnicastRoutes({}, [crib.view()]),
        ) is None

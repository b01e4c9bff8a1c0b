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


# -- entries are built by group: held to RibUnicastEntry's own __init__ ----

_ME = "n-me"
_D = 5  # links of the vantage


def _group_links():
    """`_D` links of the vantage, each with a v4 and a v6 address."""
    from openr_tpu.decision.link_state import Link

    return [
        Link(
            "0",
            _ME, Adjacency(f"nbr-{d}", f"if-{d}", metric=1,
                           next_hop_v4=f"10.0.{d}.2",
                           next_hop_v6=f"fe80::{d}:2"),
            f"nbr-{d}", Adjacency(_ME, f"if-{d}-back", metric=1,
                                  next_hop_v4=f"10.0.{d}.1",
                                  next_hop_v6=f"fe80::{d}:1"),
        )
        for d in range(_D)
    ]


def _group_matrix(announcers, v4=()):
    """A matrix's host side for rows whose announcers are `announcers[r]`
    (node names, in the order of their cells); rows in `v4` carry a v4
    prefix."""
    from types import SimpleNamespace

    from openr_tpu.types import PrefixEntry

    n = len(announcers)
    plist = [
        f"10.9.{r}.0/24" if r in v4 else f"2001:db8:{r:x}::/64"
        for r in range(n)
    ]
    return SimpleNamespace(
        prefix_list=plist,
        node_areas=[[(name, "0") for name in row] for row in announcers],
        entry_refs=[
            [PrefixEntry(prefix=plist[r], weight=100 * r + a)
             for a in range(len(row))]
            for r, row in enumerate(announcers)
        ],
        is_v4=np.asarray([r in v4 for r in range(n)], bool),
    )


def _bits(n, width, *cells):
    m = np.zeros((n, width), bool)
    for r, c in cells:
        m[r, c] = True
    return m


def _entries_by_init(links, matrix, rows, met, s3, nh, lfa_slot, lfa_metric,
                     value_rows, use_v4_allowed):
    """What build_entries has to build, through RibUnicastEntry(...) and
    NextHop(...)'s own __init__ and select_best_node_area, a row at a
    time."""
    from openr_tpu.decision.rib import NextHop, RibUnicastEntry
    from openr_tpu.decision.spf_solver import select_best_node_area

    def hop(d, use_v4, metric):
        return NextHop(
            address=links[d].nh_from_node(_ME, use_v4),
            if_name=links[d].iface_from_node(_ME),
            metric=metric,
            area=links[d].area,
            neighbor_node_name=links[d].other_node(_ME),
        )

    out = {}
    for i, p in enumerate(rows.tolist()):
        v = p if value_rows is None else int(value_rows[i])
        nas = matrix.node_areas[p]
        selected = {na for a, na in enumerate(nas) if s3[v, a]}
        if not selected:
            continue
        best = select_best_node_area(selected, _ME)
        use_v4 = use_v4_allowed and bool(matrix.is_v4[p])
        alternate = frozenset()
        if lfa_slot is not None and 0 <= lfa_slot[v] < len(links):
            alternate = frozenset(
                {hop(int(lfa_slot[v]), use_v4, int(lfa_metric[v]))}
            )
        out[matrix.prefix_list[p]] = RibUnicastEntry(
            prefix=matrix.prefix_list[p],
            nexthops=frozenset(
                hop(d, use_v4, int(met[v]))
                for d in range(len(links)) if nh[v, d]
            ),
            best_prefix_entry=matrix.entry_refs[p][nas.index(best)],
            best_node_area=best,
            igp_cost=int(met[v]),
            lfa_nexthops=alternate,
        )
    return out


def _grouped_case(case):
    """-> (matrix, rows, met, s3, nh, lfa_slot, lfa_metric, value_rows,
    use_v4_allowed, groups expected or None)."""
    a_cap = 4
    four = ["n-a", "n-b", "n-c", "n-d"]
    lfa_slot = lfa_metric = value_rows = None
    use_v4_allowed = True
    groups = None
    if case == "one_row":
        n = 1
        matrix = _group_matrix([four])
        s3 = _bits(n, a_cap, (0, 2))
        nh = _bits(n, _D, (0, 1), (0, 3))
        met = np.asarray([40], np.int32)
        groups = 1
    elif case in ("one_value", "every_row_distinct"):
        n = 24
        matrix = _group_matrix([four] * n)
        s3 = _bits(n, a_cap, *((r, r % a_cap) for r in range(n)))
        nh = _bits(n, _D, *((r, 1) for r in range(n)),
                   *((r, 4) for r in range(n)))
        met = np.full(n, 70, np.int32)
        lfa_slot = np.full(n, 2, np.int32)
        lfa_metric = np.full(n, 95, np.int32)
        groups = 1
        if case == "every_row_distinct":
            # the metric, the next hops or the alternate alone tell rows
            # apart
            met[:8] += 1 + np.arange(8, dtype=np.int32)
            nh[8:16] = [
                [(i + 1) >> d & 1 for d in range(_D)] for i in range(8)
            ]
            lfa_metric[16:] += 1 + np.arange(8, dtype=np.int32)
            lfa_slot[20:] = 3
            groups = n
    elif case.startswith(("two_selected", "three_selected")):
        k = 2 if case.startswith("two") else 3
        me_in = case.endswith("with_vantage")
        n = 12
        # the announcers differ from row to row, in name and in order,
        # and the same bits select another best among other names
        names = []
        for r in range(n):
            row = [f"n-{chr(ord('a') + (r + j) % 6)}" for j in range(a_cap)]
            if me_in:
                row[(r + 1) % a_cap] = _ME
            names.append(row if r % 2 else row[::-1])
        matrix = _group_matrix(names)
        s3 = _bits(n, a_cap, *(
            (r, (r + 1 + j) % a_cap) for r in range(n) for j in range(k)
        ))
        nh = _bits(n, _D, *((r, r % 2) for r in range(n)))
        met = np.full(n, 30, np.int32)
        groups = 2
    elif case in ("v4_allowed", "v4_over_v6"):
        n = 6
        matrix = _group_matrix([four] * n, v4={1, 2, 5})
        s3 = _bits(n, a_cap, *((r, 0) for r in range(n)))
        nh = _bits(n, _D, *((r, 2) for r in range(n)))
        met = np.full(n, 10, np.int32)
        lfa_slot = np.full(n, 0, np.int32)
        lfa_metric = np.full(n, 20, np.int32)
        use_v4_allowed = case == "v4_allowed"
        groups = 2 if use_v4_allowed else 1
    elif case in ("lfa_present", "lfa_slot_out_of_range"):
        n = 8
        matrix = _group_matrix([four] * n)
        s3 = _bits(n, a_cap, *((r, 3) for r in range(n)))
        nh = _bits(n, _D, *((r, 0) for r in range(n)))
        met = np.full(n, 10, np.int32)
        lfa_slot = np.asarray([0, 1, 1, 4, 4, 2, 2, 2], np.int32)
        lfa_metric = np.asarray([15, 15, 16, 15, 15, 15, 15, 15], np.int32)
        groups = 5
        if case == "lfa_slot_out_of_range":
            # none, past the links, and a negative that is not -1: no
            # alternate (the device writes -1 and 0 there; other bytes
            # name other groups of equal entries)
            lfa_slot[[1, 3, 5, 6]] = [-1, _D, _D + 3, -7]
            groups = 8
    elif case == "value_rows":
        # the delta path: six matrix rows out of twenty, their values at
        # positions of a payload that carries other rows' values too
        n = 20
        matrix = _group_matrix([four] * n)
        rows = np.asarray([17, 3, 11, 4, 19, 0])
        value_rows = np.asarray([5, 0, 7, 2, 3, 6])
        s3 = _bits(8, a_cap, *((v, v % a_cap) for v in range(8)),
                   (7, 0), (3, 2))
        nh = _bits(8, _D, *((v, v % _D) for v in range(8)), (2, 4))
        met = (100 + np.arange(8) // 2).astype(np.int32)
        lfa_slot = (np.arange(8) % 3 - 1).astype(np.int32)
        lfa_metric = (met + 9).astype(np.int32)
        return (matrix, rows, met, s3, nh, lfa_slot, lfa_metric,
                value_rows, True, 6)
    elif case == "no_selected_bit":
        n = 9
        matrix = _group_matrix([four] * n)
        s3 = _bits(n, a_cap, (1, 0), (4, 2), (4, 3), (8, 1))
        nh = _bits(n, _D, *((r, 1) for r in range(n)))
        met = np.full(n, 12, np.int32)
        groups = 1
    else:
        raise AssertionError(case)
    return (matrix, np.arange(n), met, s3, nh, lfa_slot, lfa_metric,
            value_rows, use_v4_allowed, groups)


@pytest.mark.parametrize("case", [
    "one_row", "one_value", "every_row_distinct",
    "two_selected_with_vantage", "two_selected_without_vantage",
    "three_selected_with_vantage", "three_selected_without_vantage",
    "v4_allowed", "v4_over_v6",
    "lfa_absent", "lfa_present", "lfa_slot_out_of_range",
    "value_rows", "no_selected_bit",
])
def test_grouped_build_matches_entries_built_by_init(case):
    """build_entries builds by group (one template a distinct value of
    the columns, the announcer from numpy): every entry equals, field for
    field and by == and hash, the one RibUnicastEntry(...) builds from
    the same row, and the two counters count entries and groups."""
    import dataclasses

    from openr_tpu.decision.columnar_rib import build_entries
    from openr_tpu.decision.rib import RibUnicastEntry

    (matrix, rows, met, s3, nh, lfa_slot, lfa_metric, value_rows,
     use_v4_allowed, groups) = _grouped_case(
        "one_value" if case == "lfa_absent" else case)
    if case == "lfa_absent":
        lfa_slot = lfa_metric = None
    links = _group_links()
    want = _entries_by_init(
        links, matrix, rows, met, s3, nh, lfa_slot, lfa_metric, value_rows,
        use_v4_allowed,
    )
    assert want, case
    built0 = _counter("decision.rib.entries_built")
    groups0 = _counter("decision.rib.entry_groups")
    got, nh_cache = {"stays": None}, {}
    counted = build_entries(
        got, nh_cache, _ME, matrix, links, rows, met, s3, nh, lfa_slot,
        lfa_metric, value_rows=value_rows, use_v4_allowed=use_v4_allowed,
    )
    assert got.pop("stays", 0) is None  # built INTO the table handed in
    assert got == want
    names = [f.name for f in dataclasses.fields(RibUnicastEntry)]
    for prefix, entry in got.items():
        ref = want[prefix]
        assert type(entry) is RibUnicastEntry
        assert list(entry.__dict__) == names == list(ref.__dict__), prefix
        for name in names:
            assert getattr(entry, name) == getattr(ref, name), (prefix, name)
        # the advertisement is the row's own object, not an equal one
        assert entry.best_prefix_entry is ref.best_prefix_entry, prefix
        assert hash(entry) == hash(ref) and entry == ref, prefix
        assert repr(entry) == repr(ref), prefix
        for hop in entry.nexthops:
            assert hop.metric == entry.igp_cost
    if case == "no_selected_bit":
        assert len(want) == 3 < len(rows)  # skipped, and not counted
    if case.endswith("with_vantage"):
        assert {e.best_node_area for e in got.values()} == {(_ME, "0")}
    if case.endswith("without_vantage"):
        assert len({e.best_node_area for e in got.values()}) > 2
    if case == "v4_allowed":
        assert {
            hop.address for p in (1, 2, 5)
            for e in [got[matrix.prefix_list[p]]]
            for hop in e.nexthops | e.lfa_nexthops
        } == {"10.0.2.2", "10.0.0.2"}
    if case == "lfa_slot_out_of_range":
        assert [bool(got[p].lfa_nexthops) for p in matrix.prefix_list] == [
            True, False, True, False, True, False, False, True]
    assert counted[0] == len(want)
    assert _counter("decision.rib.entries_built") - built0 == len(want)
    assert counted[1] == _counter("decision.rib.entry_groups") - groups0
    if groups is not None:
        assert counted[1] == groups, case
    # again over a warm nh_cache: the same table, the next hops shared
    again = {}
    build_entries(
        again, nh_cache, _ME, matrix, links, rows, met, s3, nh, lfa_slot,
        lfa_metric, value_rows=value_rows, use_v4_allowed=use_v4_allowed,
    )
    assert again == want
    for prefix, entry in again.items():
        assert entry.nexthops is got[prefix].nexthops
        assert entry.lfa_nexthops is got[prefix].lfa_nexthops or not (
            entry.lfa_nexthops)


def test_entries_of_one_group_share_no_dict():
    """A group's entries are copies of one template: each has a dict of
    its own (a store into one is not seen in another), the immutable
    next hops are the one frozenset of nh_cache, and a factory-defaulted
    field the loop does not set is called for every entry."""
    from openr_tpu.decision import columnar_rib

    (matrix, rows, met, s3, nh, lfa_slot, lfa_metric, value_rows,
     use_v4_allowed, _groups) = _grouped_case("one_value")
    links = _group_links()

    def build():
        routes = {}
        assert columnar_rib.build_entries(
            routes, {}, _ME, matrix, links, rows, met, s3, nh, lfa_slot,
            lfa_metric,
        ) == (len(rows), 1)
        return list(routes.values())

    entries = build()
    dicts = {id(e.__dict__) for e in entries}
    assert len(dicts) == len(entries)
    first, second = entries[:2]
    assert first.nexthops is second.nexthops
    assert first.lfa_nexthops is second.lfa_nexthops
    object.__setattr__(first, "counter_id", "only-mine")
    assert second.counter_id is None
    assert all(e.counter_id is None for e in entries[1:])
    saved = columnar_rib._ENTRY_FACTORIES
    columnar_rib._ENTRY_FACTORIES = [("counter_id", list)]
    try:
        entries = build()
    finally:
        columnar_rib._ENTRY_FACTORIES = saved
    assert all(e.counter_id == [] for e in entries)
    assert len({id(e.counter_id) for e in entries}) == len(entries)


def test_selected_bits_past_a_rows_names_build_no_route():
    """A generation older than a row's advertisement may select a cell
    the row no longer has: such a row is no route (and not counted), as
    a row with no bit; a bit among the names beside one past them is
    read alone."""
    from openr_tpu.decision.columnar_rib import build_entries

    matrix = _group_matrix([["n-a", "n-b"], ["n-a", "n-b"], ["n-a"]])
    links = _group_links()
    s3 = _bits(3, 4, (0, 3), (1, 1), (1, 2), (2, 0))
    nh = _bits(3, _D, (0, 0), (1, 0), (2, 0))
    met = np.full(3, 5, np.int32)
    routes = {}
    built0 = _counter("decision.rib.entries_built")
    assert build_entries(
        routes, {}, _ME, matrix, links, np.arange(3), met, s3, nh,
    ) == (2, 1)
    assert _counter("decision.rib.entries_built") - built0 == 2
    assert sorted(routes) == sorted(matrix.prefix_list[1:])
    assert routes[matrix.prefix_list[1]].best_node_area == ("n-b", "0")
    assert routes == _entries_by_init(
        links, matrix, np.arange(3), met, s3, nh, None, None, None, True)

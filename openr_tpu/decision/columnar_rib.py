"""Columnar, lazily-materialized RIB.

A profile before PR 1 showed a cold 100k-prefix rebuild spends 70% of its wall time
constructing `RibUnicastEntry` Python objects in `_build_entries` — for
routes most consumers never look at individually. This module keeps the
solver's packed device outputs (metric, selected-announcer words,
next-hop words, LFA slots) as numpy COLUMNS keyed by prefix-matrix row,
and builds entry objects only at consumption boundaries:

  - the Fib unicast diff (`fast_unicast_diff`): a journal of changed
    row-sets turns the diff into compare-only-what-the-device-says-
    changed — O(changed) entry builds instead of O(P);
  - `RibPolicy.apply_policy` / RPC serialization / CLI dumps: these
    iterate the mapping, which materializes in one bulk pass.

Three cooperating pieces:

  `ColumnarRib`   one (area, vantage)'s live column store. Mutated in
                  place by the solver (full scatter on a full result,
                  journaled as a change where a table stands; row
                  patches on steady-state deltas). Copy-on-write:
                  before a mutation, the column bundle is copied iff a
                  live `RibView` still references it, so snapshots stay
                  valid at ~2 MB/flap cost.
  `RibView`       an immutable snapshot (cols bundle + epoch) of a
                  ColumnarRib. A CURRENT view delegates to the crib's
                  shared materialization cache; a STALE view rebuilds
                  rows on demand from its retained bundle.
  `LazyUnicastRoutes`
                  the MutableMapping that DecisionRouteDb carries:
                  host-built `base` routes shadowed by per-area views,
                  with `overrides`/`deleted` capturing post-build
                  mutations (statics, RibPolicy edits) without forcing.

Entry identity is preserved exactly: `build_entries` below builds the
entries the former eager loop (`tpu_solver._build_entries`) built, field
for field, so columnar and eager materialization are byte-identical
(asserted by the property test in tests/test_columnar_rib.py, and case
by case against `RibUnicastEntry(...)`'s own `__init__`). It builds them
BY GROUP (PR 48): what a row's packed columns decide — next hops,
alternate, cost — is built once per distinct value of those columns and
copied into each entry, the announcer an entry names comes from numpy,
and the loop over the rows does only what is the row's own (prefix,
advertisement, one dict, one object, one insert). One path for one row
and for 300,000; `decision.rib.entry_groups` beside
`decision.rib.entries_built` says how many values the rows took.
"""

from __future__ import annotations

import struct
import weakref
from collections.abc import MutableMapping
from typing import Optional

import numpy as np

from openr_tpu.decision.rib import NextHop, RibUnicastEntry
from openr_tpu.decision.spf_solver import select_best_node_area
from openr_tpu.ops.edgeplan import INF32E
from openr_tpu.runtime.counters import counters

INF_E = int(INF32E)
_entry_new = object.__new__
_entry_set = object.__setattr__

# journal records retained per crib; an older snapshot falls back to the
# full per-entry compare (bounded memory, not bounded correctness)
_JOURNAL_MAX = 256


# fields the fast-construction loop in build_entries always sets itself
_ENTRY_SET_FIELDS = frozenset(
    {
        "prefix", "nexthops", "best_prefix_entry", "best_node_area",
        "igp_cost", "lfa_nexthops",
    }
)


def _entry_defaults() -> tuple[dict, list]:
    """(plain defaults, per-entry default factories) of RibUnicastEntry,
    derived from the dataclass itself so the fast constructor below
    cannot silently desynchronize when a defaulted field is added to the
    schema. The dict holds every field in the schema's order, the ones
    the loop sets as placeholders. Factory-defaulted fields the loop does
    not overwrite are CALLED PER ENTRY — sharing one factory product
    across all entries would alias a future mutable default."""
    import dataclasses

    plain = {}
    factories = []
    for f in dataclasses.fields(RibUnicastEntry):
        plain[f.name] = None  # placeholder where set per group or entry
        if f.name in _ENTRY_SET_FIELDS:
            continue
        if f.default is not dataclasses.MISSING:
            plain[f.name] = f.default
        elif f.default_factory is not dataclasses.MISSING:  # type: ignore[misc]
            factories.append((f.name, f.default_factory))  # type: ignore[misc]
        else:
            raise TypeError(
                f"RibUnicastEntry.{f.name} has no default and "
                "build_entries does not set it"
            )
    return plain, factories


_ENTRY_DEFAULTS, _ENTRY_FACTORIES = _entry_defaults()


def unpack_words(words: np.ndarray, x: int) -> np.ndarray:
    """host inverse of the device's _pack_words: int32 [R, W] -> bool
    [R, x].

    Bit extraction runs through np.unpackbits over the low two bytes of
    each little-endian word (C speed) — the shift-and-mask formulation
    materialized a [R, W, 16] int32 temporary and cost ~0.3s per 100k-row
    full pull."""
    r, wn = words.shape
    if r == 0 or wn == 0:
        return np.zeros((r, x), bool)
    low2 = (
        np.ascontiguousarray(words.astype("<i4"))
        .view(np.uint8)
        .reshape(r, wn, 4)[:, :, :2]
    )
    bits = np.unpackbits(
        np.ascontiguousarray(low2).reshape(r, wn * 2),
        axis=1,
        bitorder="little",
    )
    return bits[:, :x].astype(bool)


def pack_words_host(bits: np.ndarray) -> np.ndarray:
    """host companion of the device's _pack_words: bool [R, x] -> int32
    [R, ceil(x/16)], 16 bits per little-endian word. Used by the sharded
    fabric path, whose kernel returns unpacked masks."""
    r, x = bits.shape
    w = -(-max(x, 1) // 16)
    pad = w * 16 - x
    if pad:
        bits = np.concatenate([bits, np.zeros((r, pad), bool)], axis=1)
    by = np.packbits(
        bits.astype(np.uint8), axis=1, bitorder="little"
    )  # [R, 2w]
    out = np.zeros((r, w, 4), np.uint8)
    out[:, :, :2] = by.reshape(r, w, 2)
    return np.ascontiguousarray(out).view("<i4").reshape(r, w).astype(np.int32)


def route_ok_rows(matrix, root_idx: int, rows, met, s3, nh,
                  block_v4: bool) -> np.ndarray:
    """Vectorized route-level filter (the host mirror of the device ok
    predicate in tpu_solver._make_pipeline). met/s3/nh are indexed
    0..len(rows); `rows` (array or slice) indexes the matrix arrays."""
    ok = s3.any(axis=1) & (met < INF_E)
    if block_v4:
        ok &= ~matrix.is_v4[rows]
    ok &= ~(s3 & (matrix.ann_node[rows] == root_idx)).any(axis=1)
    eff_min = np.where(s3, matrix.min_nexthop[rows], -1).max(axis=1)
    nh_count = nh.sum(axis=1)
    ok &= (eff_min <= nh_count) & (nh_count > 0)
    return ok


def _next_hop(link, my_node_name: str, use_v4: bool, metric: int) -> NextHop:
    """The next hop over `link` as seen from this node (family-aware
    address, ref createNextHop: a v4 prefix takes the link's v4 address
    unless v4-over-v6 is on)."""
    return NextHop(
        address=link.nh_from_node(my_node_name, use_v4),
        if_name=link.iface_from_node(my_node_name),
        metric=metric,
        area=link.area,
        neighbor_node_name=link.other_node(my_node_name),
    )


_NO_LFA = frozenset()


def _group_template(key: bytes, nh_stride: int, lfa: bool, nh_cache: dict,
                    my_node_name: str, links) -> dict:
    """The entry dict of one group, everything its columns decide filled
    in: `key` is one row of build_entries' key plane (next-hop bits, then
    metric, family, and where the table has them the alternate's slot and
    metric as int32), decoded here so a group needs no row to stand for
    it. Next hops go through `nh_cache` under the keys they always had:
    the frozensets are shared by a group's entries and across events."""
    n_links = len(links)
    tail = struct.unpack_from("<4i" if lfa else "<2i", key, nh_stride)
    m, use_v4 = tail[0], bool(tail[1])
    nh_key = (key[:nh_stride], m, use_v4)
    nexthops = nh_cache.get(nh_key)
    if nexthops is None:
        # np.packbits' order: link d is bit 7 - d % 8 of byte d // 8
        nexthops = nh_cache[nh_key] = frozenset(
            _next_hop(links[d], my_node_name, use_v4, m)
            for d in range(n_links)
            if key[d >> 3] & (0x80 >> (d & 7))
        )
    lfa_nexthops = _NO_LFA
    if lfa and 0 <= tail[2] < n_links:
        slot, alt_m = tail[2], tail[3]
        lkey = ("lfa", slot, alt_m, use_v4)
        lfa_nexthops = nh_cache.get(lkey)
        if lfa_nexthops is None:
            lfa_nexthops = nh_cache[lkey] = frozenset(
                {_next_hop(links[slot], my_node_name, use_v4, alt_m)}
            )
    d = dict(_ENTRY_DEFAULTS)
    d["nexthops"] = nexthops
    d["igp_cost"] = m
    d["lfa_nexthops"] = lfa_nexthops
    return d


def _best_announcers(s3v: np.ndarray, n_sel: np.ndarray, rows_l: list,
                     node_areas: list, my_node_name: str) -> list:
    """Per row the index of the announcer whose advertisement the route
    carries: the one selected, or among several `select_best_node_area`'s
    choice (self first, else the minimum) - run once per distinct case,
    a case being the selected bits and the row's announcers by name.
    An index past the row's names (`s3v.shape[1]`) where none of them is
    selected."""
    best = s3v.argmax(axis=1).tolist()
    multi = np.flatnonzero(n_sel > 1)
    if not len(multi):
        return best
    bits = np.packbits(s3v[multi], axis=1)
    stride = bits.shape[1]
    bits_b = bits.tobytes()
    none = s3v.shape[1]
    cases: dict = {}
    for j, i in enumerate(multi.tolist()):
        nas = node_areas[rows_l[i]]
        case = (bits_b[j * stride:(j + 1) * stride], tuple(nas))
        ba = cases.get(case)
        if ba is None:
            row = s3v[i].tolist()
            sel = {na: a for a, na in enumerate(nas) if row[a]}
            ba = cases[case] = (
                sel[select_best_node_area(set(sel), my_node_name)]
                if sel else none
            )
        best[i] = ba
    return best


def build_entries(
    routes: dict, nh_cache: dict, my_node_name: str, matrix, links, rows,
    met, s3, nh, lfa_slot=None, lfa_metric=None, value_rows=None,
    use_v4_allowed: bool = True,
) -> tuple[int, int]:
    """Construct RibUnicastEntry for the given matrix rows into `routes`.
    met/s3/nh (and lfa arrays) are indexed by value_rows (delta path) or
    by matrix row (full). -> (entries built, groups resolved).

    Built by group: what a row's columns decide - next hops, alternate,
    cost - is one template dict per distinct value of the columns
    (`_group_template`), and the announcer an entry names comes from
    numpy (`_best_announcers`). The loop over the rows does what is the
    row's own: a copy of its group's template, the prefix, the
    announcer's advertisement, one RibUnicastEntry, one insert. A drain
    or an exit's shift moves tens of thousands of rows onto a handful of
    values; rows that each differ make a group a row, and the loop then
    costs what building their next hops costs."""
    vi = rows if value_rows is None else value_rows
    s3v = s3[vi]
    n_sel = s3v.sum(axis=1)
    if not n_sel.all():
        # no selected announcer, no route
        live = n_sel > 0
        rows, vi, s3v, n_sel = rows[live], vi[live], s3v[live], n_sel[live]
    n = len(rows)
    if not n:
        return 0, 0
    node_areas = matrix.node_areas
    rows_l = rows.tolist()
    best_l = _best_announcers(s3v, n_sel, rows_l, node_areas, my_node_name)
    # the key plane: a row's bytes are its group's name (next-hop bits,
    # then metric, family, alternate as int32)
    nh_bits = np.packbits(nh[vi], axis=1)
    nh_stride = nh_bits.shape[1]
    lfa = lfa_slot is not None
    plane = np.empty((n, nh_stride + (16 if lfa else 8)), np.uint8)
    plane[:, :nh_stride] = nh_bits
    tail = plane[:, nh_stride:].view("<i4")
    tail[:, 0] = met[vi]
    # a v4 prefix takes the link's v4 address unless v4-over-v6 is on
    tail[:, 1] = matrix.is_v4[rows] if use_v4_allowed else 0
    if lfa:
        tail[:, 2] = lfa_slot[vi]
        tail[:, 3] = lfa_metric[vi]
    # flat bytes and Python lists: per-row numpy indexing costs ~10x a
    # list's
    keys = plane.view(np.dtype((np.void, plane.shape[1]))).ravel().tolist()
    templates = {
        key: _group_template(
            key, nh_stride, lfa, nh_cache, my_node_name, links
        )
        for key in set(keys)
    }
    skipped = 0
    for prefix, refs, nas, ba, template in zip(
        map(matrix.prefix_list.__getitem__, rows_l),
        map(matrix.entry_refs.__getitem__, rows_l),
        map(node_areas.__getitem__, rows_l),
        best_l,
        map(templates.__getitem__, keys),
    ):
        try:
            ref = refs[ba]
        except IndexError:
            # the selected bits lie past the row's names (a generation
            # older than the row's advertisement): no route
            skipped += 1
            continue
        # bypass the dataclass __init__ (per-field object.__setattr__
        # x9): equality/hash read the same attributes either way, and
        # the template holds the schema-derived defaults
        d = template.copy()
        for fname, factory in _ENTRY_FACTORIES:
            d[fname] = factory()
        d["prefix"] = prefix
        d["best_prefix_entry"] = ref
        d["best_node_area"] = nas[ba]
        entry = _entry_new(RibUnicastEntry)
        _entry_set(entry, "__dict__", d)  # the dict itself, no copy
        routes[prefix] = entry
    built = n - skipped
    if built:
        # the zero-objects gate for the columnar spine: any hot path
        # that claims to stay in packed-array land is asserted against
        # this counter standing still
        counters.increment("decision.rib.entries_built", built)
        # how often the grouping engages: groups / built near 0 is rows
        # that read alike, near 1 traffic that bypasses it
        counters.increment("decision.rib.entry_groups", len(templates))
    return built, len(templates)


def row_index(matrix) -> dict:
    """prefix -> matrix row, built ONCE per PrefixMatrix and kept on it
    (`PrefixMatrix.row_index`; `apply_changes` keeps it up to date when
    a prefix takes or frees a row). The key index of every generation of
    every crib over the matrix is this dict plus the generation's own
    `ok` mask, so a copy-on-write epoch builds no Python key structure.
    Every O(rows) key build (there and `LazyUnicastRoutes._key_set`)
    counts in decision.crib.key_index_builds, which must stand still
    across warm epochs."""
    return matrix.row_index()


def crib_rows(matrix) -> int:
    """Rows a crib's columns hold: the rows the matrix has ever used and
    some room, so that a fresh prefix's row is a patch and not a rebuild
    (a row past them makes the solver start a new crib)."""
    n = len(matrix.prefix_list)
    return min(matrix.ann_node.shape[0], n + max(64, n >> 6))


def row_quiet(matrix, r: int) -> bool:
    """No live view of any crib over the matrix reads row `r` as a route:
    the row may take another prefix's name. What a view reads of a row —
    its name, its entry refs — it reads from the matrix as it is NOW, so
    a row that some generation still holds a route in keeps them."""
    for crib in matrix._cribs or ():
        for view in list(crib._views):
            ok = None if view.cols is None else view.cols.ok
            if ok is not None and r < len(ok) and ok[r]:
                return False
    return True


class _Cols:
    """One generation of the packed columns. Treated as immutable once a
    RibView references it (ColumnarRib copies-on-write before mutating a
    referenced bundle)."""

    __slots__ = (
        "met", "s3w", "nhw", "lfa_slot", "lfa_metric", "ok", "_key_rows",
    )

    def __init__(self):
        self.met = self.s3w = self.nhw = None
        self.lfa_slot = self.lfa_metric = None
        self.ok = None
        self._key_rows = None  # cached np.flatnonzero(ok)

    def copy(self) -> "_Cols":
        c = _Cols()
        c.met = self.met.copy()
        c.s3w = self.s3w.copy()
        c.nhw = self.nhw.copy()
        if self.lfa_slot is not None:
            c.lfa_slot = self.lfa_slot.copy()
            c.lfa_metric = self.lfa_metric.copy()
        c.ok = self.ok.copy()
        return c

    def key_rows(self) -> np.ndarray:
        if self._key_rows is None:
            self._key_rows = np.flatnonzero(self.ok)
        return self._key_rows

    def same_shape(self, other: "_Cols") -> bool:
        """Row for row comparable: as many rows, words as wide, LFA
        columns on both or on neither."""
        return (
            self.s3w.shape == other.s3w.shape
            and self.nhw.shape == other.nhw.shape
            and (self.lfa_slot is None) == (other.lfa_slot is None)
        )


def cols_changed_mask(oc: _Cols, nc: _Cols, rows) -> np.ndarray:
    """Row-wise column compare between two bundles over `rows` (an index
    array, or a slice for every row): entry construction is a pure
    function of these columns (same matrix/links per crib), so
    byte-equal rows are route-equal."""
    m = (oc.met[rows] != nc.met[rows])
    m |= (oc.s3w[rows] != nc.s3w[rows]).any(axis=1)
    m |= (oc.nhw[rows] != nc.nhw[rows]).any(axis=1)
    m |= oc.ok[rows] != nc.ok[rows]
    if oc.lfa_slot is not None and nc.lfa_slot is not None:
        m |= oc.lfa_slot[rows] != nc.lfa_slot[rows]
        m |= oc.lfa_metric[rows] != nc.lfa_metric[rows]
    elif (oc.lfa_slot is None) != (nc.lfa_slot is None):
        m |= True
    return m


class ColumnarRib:
    """One (area, vantage)'s packed route columns + shared entry cache.

    The solver mutates this in place: `set_full_packed` on a full
    result (device-compacted ok rows scattered into fresh columns),
    `apply_rows` on steady-state deltas. Every mutation bumps `epoch`
    and journals the changed row set so two RibView snapshots of the
    same crib can diff in O(changed).

    A full result is a journaled change where a table stands, a reset
    where none does. Over a standing bundle of the same shape (a warm
    vantage whose event moved more rows than a delta pull holds) the
    rows that differ are one journal entry and the entry cache is
    patched in bulk, so the epoch's diff, digest, provenance stamps and
    Fib's pass stay on the column path
    (`decision.crib.full_journaled`; the solver stamps the row count on
    its `tpu.mat` span as `full_changed_rows`). With no bundle to
    compare (the first RIB, a new crib) or one of another shape, journal
    and cache start anew (`decision.crib.full_resets`) and the next
    diff is the cold one or the entry-level compare."""

    def __init__(self, my_node_name: str, matrix, links, root_idx: int,
                 block_v4: bool, use_v4_allowed: bool, lfa: bool):
        self.my_node_name = my_node_name
        self.matrix = matrix
        self.links = links
        self.root_idx = int(root_idx)
        self.block_v4 = block_v4
        self.use_v4_allowed = use_v4_allowed
        self.lfa = lfa
        # rows of the columns, NOT a test of a live row: a row in them
        # may be free or never used (its `ok` is then False)
        self.p_n = crib_rows(matrix)
        if matrix._cribs is None:
            matrix._cribs = weakref.WeakSet()
        matrix._cribs.add(self)
        # the matrix's row changes this crib has dropped its cache for
        self.matrix_seq = matrix.touch_seq
        self.cols: Optional[_Cols] = None
        self.epoch = 0
        # oldest epoch the journal can still diff against; reset by
        # set_full_packed and by journal trimming
        self.journal_floor = 0
        # (epoch, rows, exact): `exact` marks an entry whose row set IS
        # the set of rows whose columns differ from the previous epoch
        # (a journaled full result: set_full_packed compared the two
        # bundles), not a superset a consumer must re-compare
        self.journal: list[tuple[int, np.ndarray, bool]] = []
        # (epoch, rows) whose advertisement changed in the matrix: an
        # update of the diff whatever the columns say (the entry's
        # best_prefix_entry is the advertisement)
        self.forced: list[tuple[int, np.ndarray]] = []
        self.routes: dict[str, RibUnicastEntry] = {}
        # routes is COMPLETE iff materialized; otherwise it is a partial
        # per-row cache (invalidated row-wise by apply_rows)
        self.materialized = False
        self.nh_cache: dict = {}
        # entries built from this crib's columns over its life, and the
        # groups they were built by (`build_entries`): the solver stamps
        # what an epoch's materialization gained on its `tpu.mat` span
        self.entries_built = 0
        self.entry_groups = 0
        self._views: "weakref.WeakSet[RibView]" = weakref.WeakSet()

    # -- mutation (solver side) -------------------------------------------

    def _cow(self) -> None:
        """Copy the column bundle iff a live view still references it, so
        that view's snapshot survives the coming in-place mutation."""
        c = self.cols
        if c is None:
            return
        if any(v.cols is c for v in self._views):
            self.cols = c.copy()
        else:
            # in-place mutation: the derived cache goes stale
            c._key_rows = None

    def set_full_packed(self, rows: np.ndarray, met, s3w, nhw,
                        lfa_slot=None, lfa_metric=None) -> Optional[int]:
        """A full result from the device-compacted full buffer: `rows`
        are the ok matrix rows (ascending), the value arrays their
        gathered packed outputs. Non-ok rows keep zero columns — nothing
        reads them (ok=False removes them from every view).

        Where a table of the same shape stands, the result lands as a
        journaled change: the rows whose columns differ from the
        standing bundle are one device-exact journal entry, the floor
        and the forced rows stay, and the entry cache is patched as
        `apply_rows` patches it; returns how many rows changed. Where
        none stands (the first RIB, a new crib) or the columns' shapes
        differ, the journal and the cache are reset; returns None."""
        p_n = self.p_n
        keep = rows < p_n
        rows = rows[keep]
        c = _Cols()
        c.met = np.zeros(p_n, np.int32)
        c.s3w = np.zeros((p_n, s3w.shape[1]), np.int32)
        c.nhw = np.zeros((p_n, nhw.shape[1]), np.int32)
        c.met[rows] = met[keep]
        c.s3w[rows] = s3w[keep]
        c.nhw[rows] = nhw[keep]
        if lfa_slot is not None:
            c.lfa_slot = np.full(p_n, -1, np.int32)
            c.lfa_metric = np.zeros(p_n, np.int32)
            c.lfa_slot[rows] = lfa_slot[keep]
            c.lfa_metric[rows] = lfa_metric[keep]
        c.ok = np.zeros(p_n, bool)
        c.ok[rows] = True
        old = self.cols
        self.cols = c  # old bundle stays with whatever views hold it
        self.epoch += 1
        if old is None or not old.same_shape(c):
            counters.increment("decision.crib.full_resets")
            self.journal_floor = self.epoch
            self.journal = []
            self.forced = []
            self.routes = {}
            self.materialized = False
            return None
        # a row that is a route on neither side is no change, whatever
        # an earlier patch left in its columns
        changed = np.flatnonzero(
            cols_changed_mask(old, c, slice(None)) & (old.ok | c.ok)
        )
        counters.increment("decision.crib.full_journaled")
        self.journal.append((self.epoch, changed, True))
        self._trim_journal()
        self._refresh_rows(changed)
        return len(changed)

    def _drop_rows(self, rows: np.ndarray) -> None:
        """Forget what was built of these rows."""
        plist = self.matrix.prefix_list
        pop = self.routes.pop
        for r in rows.tolist():
            pop(plist[r], None)

    def _refresh_rows(self, rows: np.ndarray) -> None:
        """Keep the entry cache coherent over `rows` of the columns as
        they stand: where it is complete, the rows that are routes built
        anew in ONE call and the others dropped; where partial, what was
        built of them dropped."""
        if self.materialized:
            ok = self.cols.ok[rows]
            self._drop_rows(rows[~ok])
            if ok.any():
                self._build_rows_into(self.cols, rows[ok], self.routes)
        elif self.routes:
            self._drop_rows(rows)

    def touch_rows(self, rows, old_names=()) -> None:
        """The matrix changed these rows (`PrefixMatrix.apply_changes`:
        an advertisement changed, a prefix took or freed the row; rows
        that took another prefix's name were called `old_names`). The
        columns are the device's to change; what was built from the rows
        goes, and the rows are journaled as forced, so the next diff
        sends each that is a route whether or not its columns moved (a
        superset of what changed: where the changed advertisement is not
        the route's best one, an equal route is sent again)."""
        rows = np.asarray(rows, np.int64)
        if not len(rows):
            return
        for name in old_names:
            self.routes.pop(name, None)
        # from the columns as they stand; what the device changes of
        # these rows, apply_rows patches after
        self._refresh_rows(rows)
        self.epoch += 1
        self.journal.append((self.epoch, rows, False))
        self.forced.append((self.epoch, rows))
        self._trim_journal()

    def _trim_journal(self) -> None:
        if len(self.journal) > _JOURNAL_MAX:
            dropped_epoch, _, _ = self.journal.pop(0)
            self.journal_floor = dropped_epoch
            while self.forced and self.forced[0][0] <= dropped_epoch:
                self.forced.pop(0)

    def set_full_arrays(self, met, s3, nh, lfa_slot=None, lfa_metric=None,
                        ok=None) -> Optional[int]:
        """A full result as UNPACKED arrays (the sharded fabric path,
        whose kernel returns bool masks + a device-computed ok); lands
        and returns as `set_full_packed`."""
        if ok is None:
            ok = route_ok_rows(
                self.matrix, self.root_idx, slice(0, len(met)),
                met, s3, nh, self.block_v4,
            )
        rows = np.flatnonzero(ok)
        return self.set_full_packed(
            rows, met[rows].astype(np.int32),
            pack_words_host(s3[rows]), pack_words_host(nh[rows]),
            None if lfa_slot is None else lfa_slot[rows].astype(np.int32),
            None if lfa_metric is None else lfa_metric[rows].astype(np.int32),
        )

    def apply_rows(self, rows: np.ndarray, met, s3w, nhw,
                   lfa_slot=None, lfa_metric=None, ok=None) -> None:
        """Steady-state delta: patch the changed rows in place (after
        copy-on-write if a snapshot is watching). When `ok` is None
        (the delta payload) the route-level filter is recomputed
        host-side, which costs an unpack of both word planes; a caller
        holding the rows' route-ok bits passes them in and the unpack
        only happens if the eager route cache needs the masks."""
        rows = np.asarray(rows)
        live = rows < self.p_n
        if not live.all():
            rows = rows[live]
            met = met[live]
            s3w = s3w[live]
            nhw = nhw[live]
            if ok is not None:
                ok = ok[live]
            if lfa_slot is not None:
                lfa_slot = lfa_slot[live]
                lfa_metric = lfa_metric[live]
        if not len(rows):
            return
        self._cow()
        c = self.cols
        a_cap = self.matrix.ann_node.shape[1]
        d_n = len(self.links)
        s3 = nhm = None
        if ok is None:
            s3 = unpack_words(s3w, a_cap)
            nhm = unpack_words(nhw, max(d_n, 1))
            ok = route_ok_rows(
                self.matrix, self.root_idx, rows, met, s3, nhm,
                self.block_v4,
            )
        c.met[rows] = met
        c.s3w[rows] = s3w
        c.nhw[rows] = nhw
        if lfa_slot is not None and c.lfa_slot is not None:
            c.lfa_slot[rows] = lfa_slot
            c.lfa_metric[rows] = lfa_metric
        c.ok[rows] = ok
        c._key_rows = None
        self.epoch += 1
        self.journal.append((self.epoch, np.asarray(rows), False))
        self._trim_journal()
        # keep the route cache coherent: eager patch when complete
        # (preserves the seed's O(changed) steady-state cost), row-wise
        # invalidation when partial
        if self.materialized:
            if s3 is None:
                s3 = unpack_words(s3w, a_cap)
                nhm = unpack_words(nhw, max(d_n, 1))
            self._drop_rows(rows[~np.asarray(ok, bool)])
            keep = np.flatnonzero(ok)
            if len(keep):
                self._count_built(build_entries(
                    self.routes, self.nh_cache, self.my_node_name,
                    self.matrix, self.links, rows[keep], met, s3, nhm,
                    lfa_slot, lfa_metric, value_rows=keep,
                    use_v4_allowed=self.use_v4_allowed,
                ))
        elif self.routes:
            self._drop_rows(rows)

    # -- reads (view side) -------------------------------------------------

    def covers(self, epoch: int) -> bool:
        return epoch >= self.journal_floor

    def changed_rows_since(self, epoch: int) -> np.ndarray:
        parts = [r for e, r, _x in self.journal if e > epoch]
        if not parts:
            return np.zeros(0, np.int64)
        return np.unique(np.concatenate(parts))

    def forced_rows_since(self, epoch: int) -> np.ndarray:
        parts = [r for e, r in self.forced if e > epoch]
        if not parts:
            return np.zeros(0, np.int64)
        return np.unique(np.concatenate(parts))

    def exact_since(self, epoch: int) -> bool:
        """True iff the journal from `epoch` to the tip is ONE exact
        entry — a journaled full result and nothing after it. Its row
        set is exact against the IMMEDIATELY preceding epoch only:
        across several epochs the union may hold rows that changed and
        changed back, which only a host re-compare filters out. When
        this holds, fast_unicast_column_diff consumes changed_rows_since
        verbatim instead of re-comparing the columns."""
        entries = [x for e, _r, x in self.journal if e > epoch]
        return len(entries) == 1 and entries[0]

    def _count_built(self, built_groups: tuple[int, int]) -> None:
        self.entries_built += built_groups[0]
        self.entry_groups += built_groups[1]

    def _build_rows_into(self, cols: _Cols, rows: np.ndarray,
                         routes: dict) -> None:
        a_cap = self.matrix.ann_node.shape[1]
        d_n = len(self.links)
        self._count_built(build_entries(
            routes, self.nh_cache, self.my_node_name, self.matrix,
            self.links, rows,
            cols.met[rows],
            unpack_words(cols.s3w[rows], a_cap),
            unpack_words(cols.nhw[rows], max(d_n, 1)),
            None if cols.lfa_slot is None else cols.lfa_slot[rows],
            None if cols.lfa_metric is None else cols.lfa_metric[rows],
            value_rows=np.arange(len(rows)),
            use_v4_allowed=self.use_v4_allowed,
        ))

    def materialize(self) -> dict:
        """Bulk-build every ok row (the consumption-boundary path)."""
        if self.materialized:
            return self.routes
        import time as _time

        t0 = _time.perf_counter()
        self.routes = {}
        rows = self.cols.key_rows()
        if len(rows):
            self._build_rows_into(self.cols, rows, self.routes)
        self.materialized = True
        counters.add_stat_value(
            "decision.crib.materialize_ms",
            (_time.perf_counter() - t0) * 1e3,
        )
        return self.routes

    def entry_for_row(self, r: int, bulk: bool = False):
        prefix = self.matrix.prefix_list[r]
        e = self.routes.get(prefix)
        if e is None and not self.materialized:
            if bulk:
                self.materialize()
            else:
                self._build_rows_into(
                    self.cols, np.asarray([r]), self.routes
                )
            e = self.routes.get(prefix)
        return e

    def view(self) -> "RibView":
        return RibView(self)


class RibView:
    """Immutable snapshot of a ColumnarRib. Current (bundle identity
    matches the crib's) -> delegates to the crib's shared cache; stale
    -> rebuilds rows on demand from its own retained bundle."""

    __slots__ = ("crib", "cols", "epoch", "_routes", "_forced",
                 "__weakref__")

    def __init__(self, crib: ColumnarRib):
        self.crib = crib
        self.cols = crib.cols
        self.epoch = crib.epoch
        self._routes: Optional[dict] = None  # own build when stale
        self._forced = False
        crib._views.add(self)

    @property
    def current(self) -> bool:
        return self.cols is self.crib.cols

    def key_rows(self) -> np.ndarray:
        return self.cols.key_rows()

    def n_rows(self) -> int:
        """Routes in this generation, without naming them."""
        return int(np.count_nonzero(self.cols.ok))

    def prefixes(self) -> list[str]:
        plist = self.crib.matrix.prefix_list
        return [plist[r] for r in self.key_rows().tolist()]

    def _row_of(self, prefix: str):
        """The prefix's row iff it is a route in THIS generation: the
        matrix's index says which row, the pinned `ok` mask whether it
        counts (a stale view answers from its own bundle, not the
        tip's)."""
        matrix = self.crib.matrix
        # the memo read inline: this runs per key of every dirty-set
        # scan and entry-level compare (tens of thousands a plane drain)
        idx = matrix._row_index
        if idx is None:
            idx = row_index(matrix)
        r = idx.get(prefix)
        ok = self.cols.ok
        # a row past this generation's columns was taken after it
        if r is None or r >= len(ok) or not ok[r]:
            return None
        return r

    def has(self, prefix: str) -> bool:
        return self._row_of(prefix) is not None

    def get(self, prefix: str, bulk: bool = True):
        r = self._row_of(prefix)
        if r is None:
            return None
        if self.current:
            return self.crib.entry_for_row(r, bulk=bulk)
        if self._routes is None:
            self._routes = {}
        e = self._routes.get(prefix)
        if e is None:
            if bulk and not self._forced:
                return self.all_routes().get(prefix)
            self.crib._build_rows_into(
                self.cols, np.asarray([r]), self._routes
            )
            e = self._routes.get(prefix)
        return e

    def get_many(self, prefixes: list) -> list:
        """`get(p, bulk=False)` for each prefix, the rows not built yet
        built in ONE call (a numpy call a row otherwise: thousands after
        a full result)."""
        crib = self.crib
        idx = row_index(crib.matrix)
        ok = self.cols.ok
        rows = np.fromiter(
            (idx.get(p, -1) for p in prefixes), np.int64, len(prefixes)
        )
        # a row past this generation's columns was taken after it
        live = (rows >= 0) & (rows < len(ok))
        live[live] = ok[rows[live]]
        if self.current:
            cache = crib.routes
            complete = crib.materialized
        else:
            if self._routes is None:
                self._routes = {}
            cache = self._routes
            complete = self._forced
        if not complete:
            lacking = np.asarray([
                r for p, r in zip(prefixes, np.where(live, rows, -1).tolist())
                if r >= 0 and p not in cache
            ], np.int64)
            if len(lacking):
                crib._build_rows_into(self.cols, lacking, cache)
        get = cache.get
        return [
            get(p) if is_route else None
            for p, is_route in zip(prefixes, live.tolist())
        ]

    def all_routes(self) -> dict:
        if self.current:
            return self.crib.materialize()
        if not self._forced:
            routes = {}
            rows = self.key_rows()
            if len(rows):
                self.crib._build_rows_into(self.cols, rows, routes)
            self._routes = routes
            self._forced = True
        return self._routes


class LazyUnicastRoutes(MutableMapping):
    """DecisionRouteDb.unicast_routes when the device path ran: host
    `base` routes shadowed by per-area RibViews, with post-build
    mutations captured in overrides/deleted (so RibPolicy edits and
    static insertions neither force materialization nor break the
    journal diff — mutated keys simply join the diff's candidate set).

    What each read costs (rows = every row of every segment, host =
    base + overrides + deleted, a handful):
      O(1)     `k in lz`, `lz[k]`/`_lookup` of one key: the matrix's
               prefix -> row index (`row_index`, built once per matrix)
               and the pinned generation's `ok` bit;
      O(host)  `bool(lz)` for any composition; `len(lz)` with at most
               one segment: `count_nonzero(ok)` corrected by the
               host-touched keys;
      O(rows)  iteration, `prefixes()`, and `len(lz)` over several
               segments (multi-area: segments may share prefixes), all
               through the cached `_key_set`; values force a bulk build.
    Equality materializes both sides (dict == LazyUnicastRoutes works
    through the reflected __eq__)."""

    __slots__ = ("base", "segments", "overrides", "deleted",
                 "_merged", "_keys")

    def __init__(self, base=None, segments=()):
        self.base: dict = dict(base) if base else {}
        self.segments: list[RibView] = list(segments)  # later wins
        self.overrides: dict = {}
        self.deleted: set = set()
        self._merged: Optional[dict] = None  # full snapshot once forced
        self._keys: Optional[dict] = None

    # -- reads -------------------------------------------------------------

    def __getitem__(self, k):
        if self._merged is not None:
            return self._merged[k]
        if k in self.deleted:
            raise KeyError(k)
        if k in self.overrides:
            return self.overrides[k]
        for seg in reversed(self.segments):
            e = seg.get(k)
            if e is not None:
                return e
        return self.base[k]

    def __contains__(self, k):
        if self._merged is not None:
            return k in self._merged
        if k in self.deleted:
            return False
        if k in self.overrides:
            return True
        return any(seg.has(k) for seg in self.segments) or k in self.base

    def _key_set(self) -> dict:
        if self._keys is None:
            counters.increment("decision.crib.key_index_builds")
            ks = dict.fromkeys(self.base)
            for seg in self.segments:
                ks.update(dict.fromkeys(seg.prefixes()))
            ks.update(dict.fromkeys(self.overrides))
            for k in self.deleted:
                ks.pop(k, None)
            self._keys = ks
        return self._keys

    def __iter__(self):
        if self._merged is not None:
            return iter(self._merged)
        return iter(self._key_set())

    def __len__(self):
        if self._merged is not None:
            return len(self._merged)
        segs = self.segments
        if len(segs) > 1:
            # segments may announce the same prefix (multi-area): no
            # cheap exact count, name the keys
            return len(self._key_set())
        # |rows ∪ host| − |deleted ∩ that|, the host-touched keys
        # resolved one by one against the segment's ok mask
        host = set(self.base) | set(self.overrides)

        def has(k):
            return any(s.has(k) for s in segs)

        n = sum(s.n_rows() for s in segs)
        n += sum(1 for k in host if not has(k))
        n -= sum(1 for k in self.deleted if k in host or has(k))
        return n

    def __bool__(self):
        """Emptiness without naming a key, exact for every composition:
        some host key survives `deleted`, or some segment holds more
        routes than `deleted` takes from it."""
        if self._merged is not None:
            return bool(self._merged)
        dl = self.deleted
        if any(k not in dl for k in self.overrides) or any(
            k not in dl for k in self.base
        ):
            return True
        return any(
            seg.n_rows() > sum(1 for k in dl if seg.has(k))
            for seg in self.segments
        )

    def lookup_many(self, keys: list) -> list:
        """`_lookup` of each key, in the same precedence, with no row
        built singly: Fib's dirty-route pass reads thousands of keys
        after a full result."""
        if self._merged is not None:
            return [self._merged.get(k) for k in keys]
        out = [self.base.get(k) for k in keys]
        for seg in self.segments:  # later wins
            for i, e in enumerate(seg.get_many(keys)):
                if e is not None:
                    out[i] = e
        if self.overrides or self.deleted:
            for i, k in enumerate(keys):
                if k in self.deleted:
                    out[i] = None
                elif k in self.overrides:
                    out[i] = self.overrides[k]
        return out

    def snapshot(self) -> "LazyUnicastRoutes":
        """Detached copy sharing the column bundles: fresh RibViews pin
        the current generation (copy-on-write protects them from future
        solver patches) while host layers are shallow-copied. O(1) in
        routes — this is how the Fib actor swaps a 100k-route desired
        state without re-keying a dict."""
        segs = []
        for s in self.segments:
            v = RibView(s.crib)
            if v.cols is not s.cols:  # pin s's generation, not the tip
                v.cols = s.cols
                v.epoch = s.epoch
            segs.append(v)
        lz = LazyUnicastRoutes(self.base, segs)
        lz.overrides = dict(self.overrides)
        lz.deleted = set(self.deleted)
        return lz

    def materialized(self) -> dict:
        """Force: one bulk build per segment, then a flat snapshot."""
        if self._merged is None:
            m = dict(self.base)
            for seg in self.segments:
                m.update(seg.all_routes())
            m.update(self.overrides)
            for k in self.deleted:
                m.pop(k, None)
            self._merged = m
        return self._merged

    # -- mutation ----------------------------------------------------------

    def __setitem__(self, k, v):
        self.deleted.discard(k)
        self.overrides[k] = v
        if self._merged is not None:
            self._merged[k] = v
        self._keys = None

    def set_host_route(self, k, v):
        """A route computed on the host after the build (Decision's
        per-prefix path): it is a host route like the build's own, so it
        joins `base`, and shadows a segment's row of the same prefix as
        any later write does."""
        self[k] = v
        self.base[k] = v

    def __delitem__(self, k):
        if k not in self:
            raise KeyError(k)
        self.overrides.pop(k, None)
        self.deleted.add(k)
        if self._merged is not None:
            self._merged.pop(k, None)
        self._keys = None

    # -- comparison --------------------------------------------------------

    def __eq__(self, other):
        if isinstance(other, LazyUnicastRoutes):
            other = other.materialized()
        if isinstance(other, dict):
            return self.materialized() == other
        return NotImplemented

    __hash__ = None  # type: ignore[assignment]

    def __repr__(self):
        n_seg = len(self.segments)
        return (
            f"LazyUnicastRoutes(len={len(self)}, segments={n_seg}, "
            f"base={len(self.base)}, overrides={len(self.overrides)})"
        )


def _lookup(lz: LazyUnicastRoutes, k):
    """Per-key resolution WITHOUT bulk-forcing a segment (the diff only
    touches O(changed) keys; a bulk build would defeat it)."""
    if lz._merged is not None:
        return lz._merged.get(k)
    if k in lz.deleted:
        return None
    v = lz.overrides.get(k)
    if v is not None:
        return v
    for seg in reversed(lz.segments):
        e = seg.get(k, bulk=False)
        if e is not None:
            return e
    return lz.base.get(k)


def fast_unicast_diff(old, new):
    """Vectorized unicast diff between two LazyUnicastRoutes built from
    the SAME cribs: the device already compared every row (the delta
    journal), so only journaled rows + host-touched keys (bases,
    overrides, deletions) need entry-level comparison. Returns
    (to_update dict, to_delete list) or None when ineligible — caller
    falls back to the full per-entry compare."""
    if not (
        isinstance(old, LazyUnicastRoutes)
        and isinstance(new, LazyUnicastRoutes)
    ):
        return None
    if len(old.segments) != len(new.segments):
        return None
    pairs = []
    for so, sn in zip(old.segments, new.segments):
        crib = sn.crib
        if so.crib is not crib:
            return None
        # the new side must be the crib's live tip (so unjournaled rows
        # are provably identical) and the old side within journal reach
        if sn.cols is not crib.cols or sn.epoch != crib.epoch:
            return None
        if not crib.covers(so.epoch):
            return None
        pairs.append((so, crib))

    candidates = (
        set(old.base) | set(new.base)
        | set(old.overrides) | set(new.overrides)
        | old.deleted | new.deleted
    )
    forced = set()
    for so, crib in pairs:
        plist = crib.matrix.prefix_list
        p_n = crib.p_n
        for r in crib.changed_rows_since(so.epoch).tolist():
            if r < p_n:
                candidates.add(plist[r])
        # a changed advertisement is an update even where the old entry,
        # rebuilt from the matrix as it is now, would compare equal
        forced.update(
            plist[r] for r in crib.forced_rows_since(so.epoch).tolist()
        )

    to_update: dict = {}
    to_delete: list = []
    for k in candidates:
        nv = _lookup(new, k)
        ov = _lookup(old, k)
        if nv is None:
            if ov is not None:
                to_delete.append(k)
        elif ov is None or ov != nv or k in forced:
            to_update[k] = nv
    to_delete.sort()
    return to_update, to_delete

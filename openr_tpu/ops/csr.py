"""The prefix x announcer matrix of one area, packed for the device.

Role in the architecture (SURVEY §7 step 3): the TPU solver does not walk
the host PrefixState per prefix — its vectorized best-route selection
reads a padded [P_cap, A_cap] announcer table rebuilt from PrefixState
whenever the announcements change. This module owns that table (the
graph itself is mirrored by ops/edgeplan.py).

Capacity classes: P_cap/A_cap round up to the next power of two so
prefix churn reuses compiled kernels instead of recompiling per prefix
count (SURVEY §7 hard part 3: dynamic topology in static shapes).

The life of a row: `build_prefix_matrix` gives every prefix of its list
a row; after that `PrefixMatrix.apply_changes` follows the changed
prefixes alone. A prefix keeps its row for as long as it is advertised.
A withdrawn prefix frees its row: the cells are cleared (no announcer,
so the device computes no route) and the row joins `free`, but it keeps
its NAME (and its entry refs) until another prefix takes it, because a
RibView of an earlier generation may still read the row as a route. A
new prefix takes its own freed row back if it still has one, else the
oldest free row that no live view reads as a route (`row_quiet`), else
the next row never used. Past `p_cap` (or an announcer count past
`a_cap`) `apply_changes` gives up and the caller builds the next
power-of-two bucket from scratch.
"""

from __future__ import annotations

from collections import deque
from dataclasses import dataclass, field
from typing import Callable, Iterable, Optional

import numpy as np

from openr_tpu.runtime.counters import counters

INF32 = np.int32(2**30)  # effectively-infinite metric, addition-safe
_NEG32 = np.int32(-(2**31))  # a preference no advertisement carries

# free rows a new prefix looks at before it takes a row never used
_FREE_PROBES = 8
# row changes a matrix remembers for the cribs over it (`touched_since`)
_TOUCH_LOG = 256


def _next_pow2(n: int, floor: int = 8) -> int:
    c = floor
    while c < n:
        c *= 2
    return c


@dataclass
class PrefixMatrix:
    """Per-prefix announcer table for vectorized best-route selection.

    Row p mirrors PrefixState.entries_for(prefix_list[p]); columns are
    announcer slots (padded to a_cap). Preferences are compared
    lexicographically on device in the reference's order
    (path_preference desc, source_preference desc, advertised distance
    asc — LsdbUtil.cpp selectRoutes:842).
    """

    # row -> prefix string, one entry a row ever used: a freed row keeps
    # its last name until another prefix takes the row
    prefix_list: list
    node_areas: list  # [p][a] -> (node, area) or None
    ann_node: np.ndarray  # int32 [P_cap, A_cap], -1 pad
    ann_valid: np.ndarray  # bool
    path_pref: np.ndarray  # int32
    source_pref: np.ndarray  # int32
    dist_adv: np.ndarray  # int32
    # host-side columns for vectorized route materialization
    min_nexthop: np.ndarray = None  # int32 [P_cap, A_cap], -1 = unset
    is_v4: np.ndarray = None  # bool [P_cap]
    # [p][a] -> PrefixEntry, aligned with node_areas: route entries are
    # materialized straight from these refs (no PrefixState lookups on
    # the hot host path)
    entry_refs: list = None
    # packed device-upload buffer memo (decision/tpu_solver._pack_matrix):
    # 5 of the 6 planes are pure functions of this matrix, so repacking
    # under overload churn rewrites only the flags segment in place
    # instead of re-concatenating all 6*P*A words
    _mbuf: np.ndarray = None
    # prefix -> row memo (`row_index`): built once and kept up to date
    # by `apply_changes`, so the columnar RIB's key index lives as long
    # as the matrix — every generation of every crib over it answers
    # "which row" from this one dict and "is it a route" from its own
    # ok mask. A freed row's name stays in it until the row is taken
    _row_index: dict = None
    # freed rows, oldest first (a dict as an ordered set)
    free: dict = field(default_factory=dict)
    # the cribs over this matrix (decision/columnar_rib, weakly): a free
    # row is taken only when no live view of any of them reads it as a
    # route
    _cribs: object = None
    # (seq, rows, names the rows had before) of the last row changes,
    # for the cribs to drop what they cached of those rows
    touch_seq: int = 0
    _touch_log: deque = field(
        default_factory=lambda: deque(maxlen=_TOUCH_LOG)
    )
    # rows whose name changed since decision/column_delta.prefix_codec
    # parsed the names
    _codec_stale: list = None
    # _AreaDevs that hold this matrix (the PrefixState memo shares it
    # between solvers): only a sole holder changes it in place
    holders: int = 0
    # valid announcer cells, and rows with two or more of them: counted
    # where the matrix is built, kept where a row is cleared and filled
    n_cells: int = 0
    n_multi: int = 0

    @property
    def n_prefixes(self) -> int:
        """Rows that hold an advertised prefix."""
        return len(self.prefix_list) - len(self.free)

    def row_index(self) -> dict:
        """prefix -> row. Every O(rows) build counts in
        decision.crib.key_index_builds, which must stand still across
        warm epochs, prefix churn included."""
        idx = self._row_index
        if idx is None:
            idx = self._row_index = {
                p: r for r, p in enumerate(self.prefix_list)
            }
            counters.increment("decision.crib.key_index_builds")
        return idx

    def touched_since(self, seq: int):
        """(rows, old names) changed after `seq`, or None where the log
        no longer reaches back."""
        if seq == self.touch_seq:
            return (), ()
        log = self._touch_log
        if not log or log[0][0] > seq + 1:
            return None
        rows: list = []
        names: list = []
        for s, r, n in log:
            if s > seq:
                rows.extend(r)
                names.extend(n)
        return rows, names

    def apply_changes(
        self,
        prefix_state,
        node_index: dict,
        area: str,
        changed: Iterable[str],
        wanted,
        row_quiet: Callable[[int], bool],
    ) -> Optional[dict]:
        """Bring the rows of the `changed` prefixes up to `prefix_state`:
        one changed advertisement changes one row's cells. `wanted` holds
        the prefixes that belong in this matrix (the area's fast-path
        prefixes); a changed prefix not in it frees its row. ->
        {"rows": changed rows, "allocated", "freed"}, or None where the
        change does not fit (no row left inside p_cap, more announcers
        than a_cap): the caller then builds a new matrix."""
        state_map = prefix_state.prefixes()
        index = self.row_index()
        free = self.free
        a_cap = self.ann_node.shape[1]
        plist = self.prefix_list
        rows: list = []
        old_names: list = []
        allocated = freed = 0
        for pfx in changed:
            want = pfx in wanted
            r = index.get(pfx)
            held = r is not None and r not in free
            if not want:
                if held:
                    self._clear_row(r)
                    free[r] = None
                    freed += 1
                    rows.append(r)
                continue
            entries = state_map.get(pfx) or {}
            anns = [
                (na, e) for na, e in sorted(entries.items())
                if na[1] == area and na[0] in node_index
            ]
            if len(anns) > a_cap:
                return None
            nas = [na for na, _ in anns]
            refs = [e for _, e in anns]
            if held and self.node_areas[r] == nas and (
                self.entry_refs[r] == refs
            ):
                continue  # changed and changed back: the row stands
            if not held:
                if r is not None:
                    del free[r]  # its own row, not taken meanwhile
                else:
                    r = self._take_row(free, row_quiet)
                    if r is None:
                        return None
                    if r < len(plist):
                        old_names.append(plist[r])
                        del index[plist[r]]
                        plist[r] = pfx
                    else:
                        plist.append(pfx)
                        self.node_areas.append(None)
                        self.entry_refs.append(None)
                    index[pfx] = r
                    self.is_v4[r] = ":" not in pfx
                    if self._codec_stale is not None:
                        self._codec_stale.append(r)
                allocated += 1
            self._clear_row(r)
            for ai, entry in enumerate(refs):
                m = entry.metrics
                self.ann_node[r, ai] = node_index[nas[ai][0]]
                self.ann_valid[r, ai] = True
                self.path_pref[r, ai] = m.path_preference
                self.source_pref[r, ai] = m.source_preference
                self.dist_adv[r, ai] = min(m.distance, int(INF32))
                self.min_nexthop[r, ai] = (
                    -1 if entry.min_nexthop is None else entry.min_nexthop
                )
            self.node_areas[r] = nas
            self.entry_refs[r] = refs
            self.n_cells += len(refs)
            self.n_multi += len(refs) >= 2
            rows.append(r)
        rows = list(dict.fromkeys(rows))
        if rows:
            self.touch_seq += 1
            self._touch_log.append((self.touch_seq, rows, old_names))
        return {"rows": rows, "allocated": allocated, "freed": freed}

    def _clear_row(self, r: int) -> None:
        """No announcer in the row's cells. Its name and its entry refs
        stay: an earlier generation's view may still read them."""
        cells = int(np.count_nonzero(self.ann_valid[r]))
        self.n_cells -= cells
        self.n_multi -= cells >= 2
        self.ann_node[r] = -1
        self.ann_valid[r] = False
        self.path_pref[r] = _NEG32
        self.source_pref[r] = _NEG32
        self.dist_adv[r] = INF32
        self.min_nexthop[r] = -1

    def _take_row(self, free: dict, row_quiet) -> Optional[int]:
        """A row for a prefix that has none: the oldest free row that no
        live view reads as a route, else the next row never used."""
        for probes, r in enumerate(free):
            if probes >= _FREE_PROBES:
                break
            if row_quiet(r):
                del free[r]
                return r
        n = len(self.prefix_list)
        return n if n < self.ann_node.shape[0] else None


def build_prefix_matrix(
    prefix_state,
    node_index: dict,
    area: str,
    prefixes: Optional[list] = None,
    p_cap: int = 0,
    a_cap: int = 0,
) -> PrefixMatrix:
    """Pack one area's announcer entries into arrays. Announcers outside
    `node_index` (not in this area's graph) are dropped — same effect as
    the solver's reachability filter for unknown nodes.

    `prefixes` entries (and prefix_state keys) are canonical strings, so
    rows read the state map directly; the common single-announcer row
    skips the announcer sort."""
    state_map = prefix_state.prefixes()
    all_prefixes = prefixes if prefixes is not None else sorted(state_map)
    rows = []
    a_max = 1
    for pfx in all_prefixes:
        entries = state_map.get(pfx) or {}
        if len(entries) == 1:
            na, e = next(iter(entries.items()))
            anns = (
                [(na, e)] if na[1] == area and na[0] in node_index else []
            )
        else:
            anns = [
                (na, e)
                for na, e in sorted(entries.items())
                if na[1] == area and na[0] in node_index
            ]
            if len(anns) > a_max:
                a_max = len(anns)
        rows.append((pfx, anns))
    p = len(rows)
    p_cap = max(p_cap, _next_pow2(max(p, 1)))
    a_cap = max(a_cap, _next_pow2(max(a_max, 1), floor=2))

    ann_node = np.full((p_cap, a_cap), -1, np.int32)
    ann_valid = np.zeros((p_cap, a_cap), bool)
    path_pref = np.full((p_cap, a_cap), _NEG32, np.int32)
    source_pref = np.full((p_cap, a_cap), _NEG32, np.int32)
    dist_adv = np.full((p_cap, a_cap), INF32, np.int32)
    min_nexthop = np.full((p_cap, a_cap), -1, np.int32)
    is_v4 = np.zeros(p_cap, bool)
    prefix_list = []
    node_areas = []
    entry_refs = []
    # cell values buffered as tuples, scattered into the padded arrays
    # in one shot (per-cell numpy scalar stores are ~10x slower at the
    # 100k-prefix scale)
    cells: list[tuple] = []
    n_multi = 0
    cell_append = cells.append
    pl_append = prefix_list.append
    na_append = node_areas.append
    er_append = entry_refs.append
    for pi, (pfx, anns) in enumerate(rows):
        pl_append(pfx)
        if len(anns) == 1:
            na, entry = anns[0]
            m = entry.metrics
            cell_append((
                pi, 0, node_index[na[0]], m.path_preference,
                m.source_preference, m.distance,
                -1 if entry.min_nexthop is None else entry.min_nexthop,
            ))
            na_append([na])
            er_append([entry])
            continue
        row_nas = []
        row_entries = []
        for ai, (na, entry) in enumerate(anns[:a_cap]):
            m = entry.metrics
            cell_append((
                pi, ai, node_index[na[0]], m.path_preference,
                m.source_preference, m.distance,
                -1 if entry.min_nexthop is None else entry.min_nexthop,
            ))
            row_nas.append(na)
            row_entries.append(entry)
        n_multi += len(row_nas) >= 2
        na_append(row_nas)
        er_append(row_entries)
    if p:
        is_v4[:p] = np.fromiter(
            (":" not in pfx for pfx in prefix_list), bool, p
        )
    if cells:
        c_pi, c_ai, c_node, c_pp, c_sp, c_da, c_mn = zip(*cells)
        pi_a = np.asarray(c_pi, np.int64)
        ai_a = np.asarray(c_ai, np.int64)
        ann_node[pi_a, ai_a] = c_node
        ann_valid[pi_a, ai_a] = True
        path_pref[pi_a, ai_a] = c_pp
        source_pref[pi_a, ai_a] = c_sp
        dist_adv[pi_a, ai_a] = np.minimum(
            np.asarray(c_da, np.int64), int(INF32)
        )
        min_nexthop[pi_a, ai_a] = c_mn
    return PrefixMatrix(
        prefix_list=prefix_list,
        node_areas=node_areas,
        ann_node=ann_node,
        ann_valid=ann_valid,
        path_pref=path_pref,
        source_pref=source_pref,
        dist_adv=dist_adv,
        min_nexthop=min_nexthop,
        is_v4=is_v4,
        entry_refs=entry_refs,
        n_cells=len(cells),
        n_multi=n_multi,
    )

"""The prefix x announcer matrix of one area, packed for the device.

Role in the architecture (SURVEY §7 step 3): the TPU solver does not walk
the host PrefixState per prefix — its vectorized best-route selection
reads a padded [P_cap, A_cap] announcer table rebuilt from PrefixState
whenever the announcements change. This module owns that table (the
graph itself is mirrored by ops/edgeplan.py).

Capacity classes: P_cap/A_cap round up to the next power of two so
prefix churn reuses compiled kernels instead of recompiling per prefix
count (SURVEY §7 hard part 3: dynamic topology in static shapes).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

import numpy as np

INF32 = np.int32(2**30)  # effectively-infinite metric, addition-safe


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

    prefix_list: list  # row -> prefix string
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
    # prefix -> row memo (decision/columnar_rib.row_index): prefix_list
    # is never mutated, so the columnar RIB's key index lives as long as
    # the matrix — every generation of every crib over it answers
    # "which row" from this one dict and "is it a route" from its own
    # ok mask
    _row_index: dict = None


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
    path_pref = np.full((p_cap, a_cap), np.int32(-(2**31)), np.int32)
    source_pref = np.full((p_cap, a_cap), np.int32(-(2**31)), np.int32)
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
    )

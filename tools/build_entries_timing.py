#!/usr/bin/env python3
"""Microseconds a row of `columnar_rib.build_entries`, on the host alone.

    python3 tools/build_entries_timing.py [--repo <checkout>] [--repeat 5]

No chip and no JAX device: a stand-in matrix of rows x 4 announcers with
one or two of them selected, 4 links, LFA columns on, the collector off
while a call is timed. Each row count (1, 32, 1,536, 8,464, 49,764: what
the benchmark's cells hand the function in one call) is timed twice: once
with at most a thousand distinct column values among the rows (what a
drain or an exit's shift leaves: the rows behind one next hop read alike)
and once with every row distinct (random metrics: traffic that bypasses
the grouping). It prints the best and the median of `--repeat` calls into
an empty table over a warm `nh_cache` (a crib keeps its next hops across
events), the first call that filled the cache, and where the function
counts them, the groups it resolved.

`solver_mat_ms` of a cell that lands a full result is this function over
`routes_moved_per_epoch` rows; where the cell reads slower a row than this
tool on the same machine, the time is not the loop's (a collection, a
starved core). `--repo` times another checkout's function (the parent's
archive) with this file's data.
"""

from __future__ import annotations

import argparse
import gc
import os
import statistics
import sys
import time
from types import SimpleNamespace

import numpy as np

ROW_COUNTS = (1, 32, 1536, 8464, 49764)
ANNOUNCERS = 4
LINKS = 4
DISTINCT = 1000
ME = "r25-acc0000"


class _Link:
    """What `build_entries` asks of a link."""

    def __init__(self, d: int):
        self.area = "25"
        self._other = f"r25-agg{d:02d}"
        self._if = f"if-{d}"

    def nh_from_node(self, node: str, is_v4: bool) -> str:
        return f"10.0.0.{self._if[3:]}" if is_v4 else f"fe80::{self._if[3:]}"

    def iface_from_node(self, node: str) -> str:
        return self._if

    def other_node(self, node: str) -> str:
        return self._other


def table(n: int, distinct: int, seed: int):
    """(matrix, columns) of `n` rows whose packed columns take at most
    `distinct` values."""
    rng = np.random.default_rng(seed)
    nas = [(f"r25-core{a}", "25") for a in range(ANNOUNCERS)]
    matrix = SimpleNamespace(
        prefix_list=[f"2001:db8:{i >> 16:x}:{i & 0xFFFF:x}::/64"
                     for i in range(n)],
        node_areas=[list(nas) for _ in range(n)],
        entry_refs=[[object() for _ in range(ANNOUNCERS)] for _ in range(n)],
        is_v4=np.zeros(n, bool),
    )
    k = min(distinct, n)
    val = rng.integers(0, k, n)
    if k == n:
        val = rng.permutation(n)
    # a value's columns: its metric names it, the rest follows from it
    met = (1000 + val).astype(np.int32)
    s3 = np.zeros((n, ANNOUNCERS), bool)
    s3[np.arange(n), val % ANNOUNCERS] = True
    s3[np.arange(n), (val // ANNOUNCERS) % ANNOUNCERS] = True  # ECMP of two
    nh = np.zeros((n, LINKS), bool)
    nh[np.arange(n), val % LINKS] = True
    nh[np.arange(n), (val // 7) % LINKS] = True
    lfa_slot = ((val // 3) % (LINKS + 1) - 1).astype(np.int32)  # -1: none
    lfa_metric = np.where(lfa_slot >= 0, met + 10, 0).astype(np.int32)
    return matrix, (met, s3, nh, lfa_slot, lfa_metric)


def time_calls(build_entries, counters, n: int, distinct: int, repeat: int):
    """-> (first call, best and median of `repeat` more, groups): the
    first call builds the next hops into an empty `nh_cache`, the others
    find them there, as a crib's events after the first of their kind."""
    matrix, (met, s3, nh, lfa_slot, lfa_metric) = table(n, distinct, seed=n)
    links = [_Link(d) for d in range(LINKS)]
    rows = np.arange(n)
    times = []
    groups = None
    nh_cache: dict = {}
    for _ in range(1 + repeat):
        routes: dict = {}
        before = counters.get_counter("decision.rib.entry_groups") or 0
        gc.collect()
        gc.disable()
        try:
            t0 = time.perf_counter()
            build_entries(
                routes, nh_cache, ME, matrix, links, rows, met, s3, nh,
                lfa_slot, lfa_metric, value_rows=rows,
            )
            times.append(time.perf_counter() - t0)
        finally:
            gc.enable()
        assert len(routes) == n, (len(routes), n)
        after = counters.get_counter("decision.rib.entry_groups")
        if after is not None:
            groups = int(after - before)
    return times[0], min(times[1:]), statistics.median(times[1:]), groups


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--repo", default=os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))
    ap.add_argument("--repeat", type=int, default=5)
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.abspath(args.repo))
    from openr_tpu.decision.columnar_rib import build_entries
    from openr_tpu.runtime.counters import counters

    print(f"build_entries of {os.path.abspath(args.repo)}: us a row, best / "
          f"median of {args.repeat} calls over a warm nh_cache; the first "
          "call, which fills it, in ms")
    for label, distinct in ((f"<= {DISTINCT} values", DISTINCT),
                            ("every row distinct", None)):
        for n in ROW_COUNTS:
            first, best, med, groups = time_calls(
                build_entries, counters, n, distinct or n, args.repeat)
            print(
                f"  {label:>18}  rows {n:>6}  "
                f"{best / n * 1e6:7.2f} / {med / n * 1e6:7.2f} us a row  "
                f"({best * 1e3:8.3f} ms best, first {first * 1e3:8.3f})  "
                f"groups {'-' if groups is None else groups}"
            )
    return 0


if __name__ == "__main__":
    sys.exit(main())

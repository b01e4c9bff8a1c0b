#!/usr/bin/env python3
"""One run of a benchmark cell with its holds of the event loop written out.

    python3 tools/hold_report.py --workload <cell> --seed <n> --seconds 45 \
        --trace 1 [--root ...] [--rehearse]

Runs `benchmark/run.py`'s `main` with the same arguments (so the run's own
lines, the result line among them, are printed as ever) and then writes
`chiprun_out/holds.<cell>.<seed>.json`: every hold the tracer's background
track kept from the process's start (`tracer.get_holds()`), the measured
window's bounds and events (due, sent, acked), the harness's own stamps
of the collector — what PERF.md's section 5 and 6 quote per hold — and
`window_counters`, what the solver's `decision.tpu.*` counters (`epochs`,
`cold_compactions`, `cone_passes`, `cone_skips` and, of the prefix rows,
`prefix_rows_changed`, `prefix_only_epochs`, `prefix_matrix_rebuilds`,
`candidate_epochs`, `candidate_rows`: the epochs whose row stages ran over
candidate rows — the prefix-only ones since PR 42, and since PR 44 the
incremental solves that found their moved node columns to reach no more
rows than a delta pull holds — and those rows; `wide_epochs`, the
incremental solves that looked at every row, and `wide_epochs.<reason>`)
and `decision.crib.key_index_builds` gained over that window, with how
the window's full results landed (`decision.crib.full_journaled`,
`.full_resets`), how many of its diffs were warm and columnar or
journal-bounded (`decision.fast_unicast_diffs`), and the route entries it
built with the groups they were built by (`decision.rib.entries_built`,
`.entry_groups`: groups / built near 0 is rows that read alike). A
builder's tool: it edits nothing of the benchmark and the program has no
such exporter.
"""

from __future__ import annotations

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
OUT = os.path.join(ROOT, "chiprun_out")
sys.path[:0] = [os.path.join(ROOT, "benchmark"), ROOT]

import run  # noqa: E402  (stamps T_PROCESS)

# of `decision.tpu.*`, the counters (the rest are gauges of the mirror),
# the one that counts O(rows) key structures built, and how full results
# landed in the columnar RIB and were diffed
WINDOW_COUNTERS = tuple(f"decision.tpu.{name}" for name in (
    "epochs", "cold_compactions", "cone_passes", "cone_skips",
    "prefix_rows_changed", "prefix_only_epochs", "prefix_matrix_rebuilds",
    "candidate_epochs", "candidate_rows", "wide_epochs",
)) + (
    "decision.crib.key_index_builds", "decision.crib.full_journaled",
    "decision.crib.full_resets", "decision.fast_unicast_diffs",
    "decision.rib.entries_built", "decision.rib.entry_groups",
)
WIDE_REASONS = "decision.tpu.wide_epochs."


def main(argv=None) -> int:
    import harness
    from openr_tpu.runtime.counters import counters
    from openr_tpu.runtime.tracing import tracer

    args = run.parse_args(argv)
    kept: dict = {}
    window = harness.Session.window

    async def keep(self, *a, **kw):
        before = counters.raw_counters()
        result = await window(self, *a, **kw)
        if "sample_seed" in kw:  # the measured window, not the warm-up's
            kept.update(
                window=result, collections=list(self.collections),
                counters={
                    key.removeprefix("decision.tpu."):
                        value - before.get(key, 0)
                    for key, value in counters.raw_counters().items()
                    if key in WINDOW_COUNTERS or key.startswith(WIDE_REASONS)
                },
            )
        return result

    harness.Session.window = keep
    try:
        rc = run.main(argv)
    finally:
        harness.Session.window = window

    w = kept.get("window") or {}
    out = {
        "workload": args.workload, "seed": args.seed, "rc": rc,
        "t_process": run.T_PROCESS,
        "window": {k: w.get(k) for k in ("start", "end", "seconds")},
        "events": [
            {k: ev.get(k) for k in
             ("due", "sent", "acked", "timed", "class", "ack_epoch")}
            for ev in w.get("events", ())
        ],
        "harness_collections": kept.get("collections", []),
        "holds": tracer.get_holds(),
        "holds_dropped": tracer.holds_dropped,
        "counters": {
            **counters.get_counters("runtime.gc."),
            **counters.get_counters("tracing.holds"),
        },
        "loop_lag_ms": counters.get_statistics("runtime.loop_lag_ms"),
        "window_counters": kept.get("counters", {}),
    }
    os.makedirs(OUT, exist_ok=True)
    path = os.path.join(OUT, f"holds.{args.workload}.{args.seed}.json")
    with open(path, "w") as f:
        json.dump(out, f)
    print(json.dumps({
        "holds_written": path, "holds": len(out["holds"]),
        "window_counters": out["window_counters"],
    }), file=sys.stderr)
    return rc


if __name__ == "__main__":
    sys.exit(main())

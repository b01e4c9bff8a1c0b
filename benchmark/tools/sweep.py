#!/usr/bin/env python3
"""Find a cell's knee: one set-up, then one short window at each of several
periods, slowest first, each a further part of the same plan.

    python3 benchmark/tools/sweep.py --workload lsdb100k.flap --seed 1 \\
        --periods-ms 400,250,200,150,125,100 --seconds 15

One JSON line per period (events, events per epoch, how late the generator
ran, the median, 95th percentile and maximum of churn-to-ack, compiles in
the window), then the whole-table comparison on the final LSDB. The knee is
the highest rate at which lateness and events per epoch stay flat and the
median stays within a tenth of the slowest rate's.
"""

from __future__ import annotations

import argparse
import asyncio
import os
import sys

_HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [_HERE, os.path.dirname(_HERE)]

import files  # noqa: E402
import harness  # noqa: E402
import metrics  # noqa: E402
import run  # noqa: E402


async def sweep(args, config, traffic, lsdb, platform):
    session = harness.Session(config, traffic, args.seed, lsdb, args.root)
    periods = [float(p) / 1e3 for p in args.periods_ms.split(",")]
    try:
        await session.boot()
        await session.settle()
        await session.warm_up(periods[0])
        run.emit(phases_s={k: round(v, 3) for k, v in session.phases.items()})
        for period_s in periods:
            window = await session.window(args.seconds, period_s)
            s = metrics.series_of(window, {}, {}, {})
            run.emit(
                period_ms=period_s * 1e3,
                events=len(window["events"]), failed=window["failed"],
                events_per_epoch=len(window["events"])
                / max(1, s["window.epochs"][0]),
                late_p95_ms=metrics.percentile(s["event.late_ms"], 95),
                ack_ms_p50=metrics.percentile(s["event.ack_ms"], 50),
                ack_ms_p95=metrics.percentile(s["event.ack_ms"], 95),
                ack_ms_max=max(s["event.ack_ms"], default=None),
                ack_ms_median_by_class=metrics.medians_by(s),
                exec_ms_mean=metrics.REDUCTIONS["mean"](s["epoch.exec_ms"]),
                compiled_in_window=window["compiles"],
                gc2_pauses_ms=s["host.gc2_pause_ms"],
            )
        verdict = session.verify(window, platform)
        run.emit(
            checks=verdict["checks"], no_hiding=verdict["no_hiding"],
            tables_identical=verdict["tables_identical"],
            overload=session.overload_counters(),
        )
    finally:
        await session.close()


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--periods-ms", required=True)
    p.add_argument("--seconds", type=float, default=15.0)
    p.add_argument("--rehearse", action="store_true")
    p.add_argument("--root", default=files.ROOT)
    args = p.parse_args(argv)
    c = run.open_cell(args)
    asyncio.run(sweep(
        args, c["config"], c["traffic"], c["lsdb"], c["device"]["platform"]
    ))
    return 0


if __name__ == "__main__":
    sys.exit(main())

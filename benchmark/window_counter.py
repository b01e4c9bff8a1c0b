"""What a counter of the program gained over the window, for the readers
of metrics that are a counter's gain over the window's epochs.

`series` carries no counter. Where the program stamps every addition to a
counter as a sample of the stat of the same name (`TpuSpfSolver._count`),
the samples carry the host's monotonic clock, and those since the window's
start (`loop_holds.window_bounds`: run.py's T_PROCESS + setup.setup_s) are
the window's gain: nothing is added after the window, whose events are all
acked before it closes."""

from __future__ import annotations

import time

import loop_holds


def gained(series: dict, key: str):
    """Sum of the additions to counter `key` since the window's start, or
    None: no window observed, no program, a program without the stat."""
    if not series.get("window.epochs"):
        return None
    bounds = loop_holds.window_bounds(series)
    if bounds is None:
        return None
    try:
        from openr_tpu.runtime.counters import counters
    except ImportError:
        return None
    if counters.get_counter(key) is None:
        return None
    age = time.monotonic() - bounds[0]
    stats = counters.get_statistics(key, windows=(age,)).get(key)
    if not stats:
        return None
    window = stats[str(int(age))]
    return None if window["truncated"] else window["sum"]


def per_epoch(series: dict, key: str):
    gain = gained(series, key)
    epochs = (series.get("window.epochs") or [0])[-1]
    return None if gain is None or not epochs else gain / epochs

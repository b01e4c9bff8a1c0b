"""What the readers of the `event loop` layer share: the program's
background track of holds (`openr_tpu.runtime.tracing`, `tracer.get_holds`)
and the window's bounds.

A hold is a stretch in which work that belongs to no event kept the one
event loop: the interpreter's collector (`runtime.gc`), KvStore's digest
beacon (`kvstore.digest`), the flap damper's sweep
(`decision.damper_sweep`), and what the actors' heartbeat saw but nobody
named (`runtime.unnamed_hold`). The program copies each into the traces it
delayed, so `metrics.series_of` carries those as `span.<name>` like any
stage; the window-level readers ask the tracer's ring itself.

`series` carries no absolute time. The window's start is run.py's
`T_PROCESS` + `setup.setup_s` (taken just before `Session.window` lays
out its events, so it is early by what that takes: tens of ms). Its length
is the traced window's own (`device.window_ms`: first send to last ack;
the profiler's stop, seconds of a held loop, begins right there), or
`window.seconds` where no trace was reduced. Every reader gives None on a
program without the track, and where no window was observed.
"""

from __future__ import annotations

import os
import sys


def track():
    """The program's tracer where it keeps holds, else None (the parent
    of the PR that added the track; no program at all)."""
    try:
        from openr_tpu.runtime.tracing import tracer
    except ImportError:
        return None
    return tracer if hasattr(tracer, "get_holds") else None


def per_timed_event(series: dict, name: str):
    """Sum of the copies of hold `name` in the traces of the carrying
    epochs over the window's timed events, in ms: what the hold added to
    a timed event in the mean, beside the stage spans it was charged to.
    0.0 where none fell in an event."""
    if not series.get("window.epochs") or track() is None:
        return None
    events = len(series.get("event.ack_ms") or ())
    if not events:
        return None
    return sum(series.get(f"span.{name}", ())) / events


def t_process():
    """run.py's stamp of the process's start, whichever name the module
    runs under (`__main__` from the command line, `run` in a test)."""
    for name in ("__main__", "run"):
        module = sys.modules.get(name)
        path = getattr(module, "__file__", None) or ""
        if os.path.basename(path) == "run.py" and hasattr(module, "T_PROCESS"):
            return module.T_PROCESS
    return None


def window_bounds(series: dict):
    """(start, end) of the window on time.monotonic(), or None."""
    t0 = t_process()
    setup = series.get("setup.setup_s")
    length = window_seconds(series)
    if t0 is None or not setup or not length:
        return None
    start = t0 + setup[-1]
    return start, start + length


def window_seconds(series: dict):
    traced = series.get("device.window_ms")
    if traced and traced[-1]:
        return traced[-1] / 1e3
    seconds = series.get("window.seconds")
    return seconds[-1] if seconds else None


def _observed(series: dict):
    """(tracer, start, end) where a window was observed on a program
    with the track, else None."""
    tracer, bounds = track(), window_bounds(series)
    if not series.get("window.epochs") or tracer is None or bounds is None:
        return None
    return (tracer, *bounds)


def _complete_since(tracer, t: float) -> bool:
    """Whether the ring still holds every hold that ended after `t`: it
    drops its oldest, so what ended before its first entry may be gone."""
    if not tracer.holds_dropped:
        return True
    holds = tracer.get_holds()
    return bool(holds) and holds[0]["end"] <= t


def holds_in_window(series: dict):
    """The holds that overlap the window, each clipped to it, as sorted
    (start, end) pairs; None without a window, a track, or a whole ring."""
    observed = _observed(series)
    if observed is None:
        return None
    tracer, start, end = observed
    if not _complete_since(tracer, start):
        return None
    return sorted(
        (max(h["start"], start), min(h["end"], end))
        for h in tracer.get_holds(since=start, until=end)
    )


def holds_before_window(series: dict, name: str):
    """Seconds of hold `name` that ended before the window's start; None
    where the ring has dropped anything."""
    observed = _observed(series)
    if observed is None or observed[0].holds_dropped:
        return None
    tracer, start, _ = observed
    return sum(
        h["end"] - h["start"] for h in tracer.get_holds(until=start)
        if h["name"] == name and h["end"] <= start
    )

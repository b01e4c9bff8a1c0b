"""Share of the window in which a hold kept the event loop, in per cent:
the union of all holds of the tracer's background track inside the window
(`runtime.gc`, `kvstore.digest`, `decision.damper_sweep`,
`runtime.unnamed_hold`) over the window's length. What `waiting_for_event`
lumps with true idleness. None on a program without the track, or where
the ring dropped holds of the window."""

from loop_holds import holds_in_window, window_seconds


def read(series: dict):
    holds = holds_in_window(series)
    if holds is None:
        return None
    held, at = 0.0, float("-inf")
    for start, end in holds:
        held += max(0.0, end - max(start, at))
        at = max(at, end)
    return 100.0 * held / window_seconds(series)

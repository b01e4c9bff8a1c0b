"""The longest single hold of the event loop inside the window, in ms
(clipped to the window): what `ack_ms_max` and `gen_late_p95_ms` follow.
0.0 in a window with no hold; None on a program without the track."""

from loop_holds import holds_in_window


def read(series: dict):
    holds = holds_in_window(series)
    if holds is None:
        return None
    return 1e3 * max((end - start for start, end in holds), default=0.0)

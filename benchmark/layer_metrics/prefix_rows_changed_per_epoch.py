"""Rows of the device's announcer matrix scattered in the window, over the
window's solve epochs: 1.0 where every event is one prefix's row and no
two events shared an epoch. What `decision.tpu.prefix_rows_changed` gained
over the window: the program stamps every addition to that counter as a
sample of the stat of the same name, and the samples since the window's
start (loop_holds.window_bounds) are summed. A program without the
counter, or a call with no window observed, gives None."""

import window_counter


def read(series: dict):
    return window_counter.per_epoch(
        series, "decision.tpu.prefix_rows_changed"
    )

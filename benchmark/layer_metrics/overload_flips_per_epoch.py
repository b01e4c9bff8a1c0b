"""Nodes whose drain bit changed in the solver's mirror in the window, over
the window's solve epochs, timed or not: what `decision.tpu.overload_flips`
gained. 1.0 where every event drains one switch or gives one back and no
two events shared an epoch; 0 under link or prefix events. Read as
prefix_rows_changed_per_epoch reads its counter. A program without the
counter, or a call with no window observed, gives None."""

import window_counter


def read(series: dict):
    return window_counter.per_epoch(series, "decision.tpu.overload_flips")

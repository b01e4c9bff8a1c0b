"""Rows a full result changed in the columnar RIB in the window, over the
window's solve epochs: what `decision.tpu.full_changed_rows` gained (every
full pull that lands on a warm vantage as a journaled change adds the rows
it journaled; the program stamps every addition as a sample of the stat of
the same name, read as prefix_rows_changed_per_epoch reads its counter).
0 where every epoch's changes fit a delta pull; 49,000 where one event
moves every inter-area route. A program without the counter, or a call with
no window observed, gives None."""

import window_counter


def read(series: dict):
    return window_counter.per_epoch(
        series, "decision.tpu.full_changed_rows"
    )

"""Share of the window's solve epochs, in per cent, in which an incremental
solve's row stages looked at every row and not at candidate rows: what
`decision.tpu.wide_epochs` gained over the window (stamped, read as
prefix_rows_changed_per_epoch reads its counter) over the window's epochs.
100 where every event moves what every row shares (a metric of the
vantage's own link) or more rows than a delta pull holds; 0 where the
candidates' path took every epoch. A program without the counter, or a call
with no window observed, gives None."""

import window_counter


def read(series: dict):
    share = window_counter.per_epoch(series, "decision.tpu.wide_epochs")
    return None if share is None else 100.0 * share

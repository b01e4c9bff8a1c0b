"""Share of the window's solve epochs, in per cent, that were prefix-only
solves on the device: no relaxation, the row stages over the resident
plane (`decision.tpu.prefix_only_epochs`, read as
prefix_rows_changed_per_epoch reads its counter). 100 where every event
is a prefix event and the device took each; 0 under link events. A program
without the counter, or a call with no window observed, gives None."""

import window_counter


def read(series: dict):
    share = window_counter.per_epoch(
        series, "decision.tpu.prefix_only_epochs"
    )
    return None if share is None else 100.0 * share

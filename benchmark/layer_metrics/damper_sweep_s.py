"""Seconds the flap damper's sweep held the event loop before the window
began: the sum of the `decision.damper_sweep` holds that ended by then.
After a full sync the damper walks a record for every loaded key once a
tick until it has forgotten them, and the harness's settle waits that out:
this is the sweep's share of `setup_s`. None on a program without the
track, or where the ring dropped any hold."""

from loop_holds import holds_before_window


def read(series: dict):
    return holds_before_window(series, "decision.damper_sweep")

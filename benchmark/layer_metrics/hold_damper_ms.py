"""Milliseconds a timed event was held by the flap damper's sweep
(`FlapDamper.releasable()` over every record, once a tick): the sum of the
`decision.damper_sweep` hold spans the program copied into the traces of
the carrying epochs, over the window's timed events. A mean per timed event
like every span metric, so it stands beside the stage the hold was charged
to. 0.0 where the program keeps holds and none fell in an event; None (left
out) on a program without the track."""

from loop_holds import per_timed_event


def read(series: dict):
    return per_timed_event(series, "decision.damper_sweep")

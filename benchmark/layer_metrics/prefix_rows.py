"""Rows of the device's [rows, advertisers] prefix plane, at the window's
end: `decision.tpu.prefix_rows`, the gauge the program sets where it packs
the announcer matrix and places it on the device. One row a prefix, padded
to a power of two; every stage after the SSSP (unpack, select, nexthop,
lfa, pack, diff, compact) works over all of them, whatever they hold and
however few changed. A program without the gauge (the parent of the PR
that added it), or a call with no window observed, gives None."""


def read(series: dict):
    if not series.get("window.epochs"):
        return None
    from openr_tpu.runtime.counters import counters

    rows = counters.get_counter("decision.tpu.prefix_rows")
    return rows or None

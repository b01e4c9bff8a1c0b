"""Advertisers a prefix row holds on the device, at the window's end: the
gauge `decision.tpu.announcer_cells` (valid cells of the announcer planes,
set where the vantage's root table is placed, every solve) over
`decision.tpu.prefixes` (rows that hold a prefix). 1.0 where every prefix
has one advertiser, as in every cell before wan50k_region; 3.94 where
49,000 of 50,000 prefixes reach an area from its four border routers. Every
row stage pays for `decision.tpu.announcer_slots` cells a row whatever this
reads. A program without the gauge (the parent of the PR that added it), or
a call with no window observed, gives None."""


def read(series: dict):
    if not series.get("window.epochs"):
        return None
    from openr_tpu.runtime.counters import counters

    cells = counters.get_counter("decision.tpu.announcer_cells")
    prefixes = counters.get_counter("decision.tpu.prefixes")
    if not cells or not prefixes:
        return None
    return cells / prefixes

"""Lanes of the device's [lanes, nodes] distance plane, at the window's
end: `decision.tpu.spf_lanes`, the gauge the program sets where it places
the vantage's root table. One lane a link of the vantage (SSSP from the
link's far end in the graph without the vantage), padded to a power of two
of at least 4; every pass of the relaxation, the cone walk and the parent
plane gathers all of them, whatever they hold. A program without the gauge
(the parent of the PR that added it), or a call with no window observed,
gives None."""


def read(series: dict):
    if not series.get("window.epochs"):
        return None
    from openr_tpu.runtime.counters import counters

    lanes = counters.get_counter("decision.tpu.spf_lanes")
    return lanes or None

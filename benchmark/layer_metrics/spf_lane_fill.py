"""Share of the distance plane's lanes that hold a link of the vantage, in
per cent, at the window's end: `decision.tpu.spf_sources` over
`decision.tpu.spf_lanes`. The rest is padding to the next power of two
that every pass still computes. A program without the gauges, or a call
with no window observed, gives None."""


def read(series: dict):
    if not series.get("window.epochs"):
        return None
    from openr_tpu.runtime.counters import counters

    sources = counters.get_counter("decision.tpu.spf_sources")
    lanes = counters.get_counter("decision.tpu.spf_lanes")
    if sources is None or not lanes:
        return None
    return 100.0 * sources / lanes

"""Share of the prefix plane's rows that hold a prefix, in per cent, at
the window's end: `decision.tpu.prefixes` over `decision.tpu.prefix_rows`.
The rest is padding to the next power of two that every row stage still
computes. A program without the gauges, or a call with no window observed,
gives None."""


def read(series: dict):
    if not series.get("window.epochs"):
        return None
    from openr_tpu.runtime.counters import counters

    prefixes = counters.get_counter("decision.tpu.prefixes")
    rows = counters.get_counter("decision.tpu.prefix_rows")
    if prefixes is None or not rows:
        return None
    return 100.0 * prefixes / rows

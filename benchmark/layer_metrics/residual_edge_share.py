"""Share of the graph's directed edges that the solver's device mirror
holds in its residual ELL (relaxed by gathers) and not in a shift class
(relaxed by rolls), in per cent, at the window's end: the gauges
`decision.tpu.residual_edges` and `decision.tpu.shift_edges`, fetched from
the program's own registry (metrics.py hands readers durations only). A
program without the gauges, or a call with no window observed, gives
None."""


def read(series: dict):
    if not series.get("window.epochs"):
        return None
    from openr_tpu.runtime.counters import counters

    residual = counters.get_counter("decision.tpu.residual_edges")
    shift = counters.get_counter("decision.tpu.shift_edges")
    if residual is None or shift is None or not residual + shift:
        return None
    return 100.0 * residual / (residual + shift)

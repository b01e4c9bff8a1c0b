"""Share of the residual ELL's padded slots that hold an edge, in per
cent, at the window's end: `decision.tpu.residual_edges` over
`decision.tpu.residual_r_cap` x `decision.tpu.residual_k_cap`. Every round
of the relaxation gathers all r_cap x k_cap slots for each SPF source,
whatever they hold: the rest is padding to the widest row and to a power of
two. A program without the gauges, a mirror with no residual, or a call
with no window observed, gives None."""


def read(series: dict):
    if not series.get("window.epochs"):
        return None
    from openr_tpu.runtime.counters import counters

    edges = counters.get_counter("decision.tpu.residual_edges")
    r_cap = counters.get_counter("decision.tpu.residual_r_cap")
    k_cap = counters.get_counter("decision.tpu.residual_k_cap")
    if edges is None or not r_cap or not k_cap:
        return None
    return 100.0 * edges / (r_cap * k_cap)

"""Share of the routes in the solver's table that carry a loop-free
alternate (RFC 5286), in per cent, at the window's end:
`decision.lfa.routes_with_backup` over `decision.lfa.routes`, gauges the
program sets where it patches the table. A program without them, or with
LFA off, or a call with no window observed, gives None."""


def read(series: dict):
    if not series.get("window.epochs"):
        return None
    from openr_tpu.runtime.counters import counters

    backed = counters.get_counter("decision.lfa.routes_with_backup")
    routes = counters.get_counter("decision.lfa.routes")
    if backed is None or not routes:
        return None
    return 100.0 * backed / routes

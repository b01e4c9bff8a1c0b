"""Share of the traced window in which no operation ran on the device, in
per cent: 100 * (1 - busy / window), from the profiler's trace."""


def read(series: dict):
    busy, window = series.get("device.busy_ms"), series.get("device.window_ms")
    if not busy or not window or not window[-1]:
        return None
    return 100.0 * (1.0 - busy[-1] / window[-1])

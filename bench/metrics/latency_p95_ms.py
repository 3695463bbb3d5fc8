"""95th percentile of the latency of every request of the window, in ms
(``statistics.quantiles``, exclusive method)."""

import statistics


def read(ctx):
    lat = [a.latency_s * 1e3 for a in ctx.window.answers]
    if len(lat) < 2:
        return None
    return statistics.quantiles(lat, n=20)[18]

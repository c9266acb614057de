"""A quantile of a series the driver's own client recorded over the
window: a number per request that completed inside it (a latency, a
lag), or a reading taken at intervals (the pool's share in use, a
tenth's rate over the median tenth's). ``q`` 0 is the least of them.

params: ``series`` (key of the driver's client records), ``q``,
``scale`` (multiplier, default 1).
"""


def read(readings, params):
    from benchmark import harness

    values = readings.client.get(params["series"]) or []
    if not values:
        return None
    return float(params.get("scale", 1.0)) * harness.quantile(
        values, float(params["q"]))

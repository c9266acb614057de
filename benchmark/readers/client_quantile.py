"""A quantile of a per-request number the driver's own client recorded
for the requests that completed inside the window.

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

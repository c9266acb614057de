"""A quantile of the durations of one of the program's host spans that
lie inside the window, in milliseconds.

params: ``span`` (name), ``q`` (0..1).
"""


def read(readings, params):
    from benchmark import harness

    spans = readings.spans.named(params["span"], readings.window)
    if not spans:
        return None
    return 1e3 * harness.quantile([s["dur"] for s in spans],
                                  float(params["q"]))

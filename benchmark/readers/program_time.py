"""A quantile of the device time of one compiled program per execution,
from the device trace's ``XLA Modules`` line, in milliseconds. On several
chips every chip's executions count.

params: ``program`` (regular expression on the program's name), ``q``.
"""

import re


def durations(readings, pattern):
    if readings.trace is None:
        return []
    rx = re.compile(pattern)
    return [d for name, ds in readings.trace["programs"].items()
            if rx.search(name) for d in ds]


def read(readings, params):
    from benchmark import harness

    ds = durations(readings, params["program"])
    if not ds:
        return None
    return 1e3 * harness.quantile(ds, float(params["q"]))

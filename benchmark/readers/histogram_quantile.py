"""A quantile of what one of the program's histograms observed during
the window, by linear interpolation inside the bucket (coarse: the
buckets are the program's), in milliseconds.

params: ``histogram`` (family name, observations in seconds), ``q``.
"""


def read(readings, params):
    from benchmark import harness

    after = harness.histogram_buckets(readings.after, params["histogram"])
    before = harness.histogram_buckets(readings.before, params["histogram"])
    cum = sorted((b, c - before.get(b, 0.0)) for b, c in after.items())
    if not cum or cum[-1][1] <= 0:
        return None
    rank = float(params["q"]) * cum[-1][1]
    lo_bound, lo_count = 0.0, 0.0
    for bound, count in cum:
        if count >= rank:
            if bound == float("inf"):
                return 1e3 * lo_bound  # beyond the last bucket: its edge
            inside = count - lo_count
            share = (rank - lo_count) / inside if inside > 0 else 1.0
            return 1e3 * (lo_bound + share * (bound - lo_bound))
        lo_bound, lo_count = bound, count
    return None

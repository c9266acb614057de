"""The share (in %) of the window that the program's host spans of some
names fill: the sum of the durations of the ``spans`` names' spans that
lie wholly inside the window (as ``span_quantile`` counts them), less
the ``minus`` names' spans that lie inside one of those, over the
window's length. With leaf spans that do not overlap it is the share of
the time the host spent in those phases.

``where`` narrows a name to the spans whose arguments read as given
(``{"executor.run_block": {"synced": false}}`` counts a dispatch only
where the span times the enqueue alone, not the wait for the device); a
span that lacks the argument is not counted.

Every name of ``spans`` has to be there: a program that lacks one of
them (an older one) reads nothing rather than a part of the sum.

params: ``spans`` (names, added), ``minus`` (names, subtracted),
``where`` (name -> arguments a counted span must carry).
"""


def read(readings, params):
    lo, hi = readings.window
    where = params.get("where", {})
    counted = []
    for name in params["spans"]:
        found = readings.spans.named(name, readings.window)
        if not found:
            return None
        need = where.get(name, {})
        counted.extend(
            (s["start"], s["start"] + s["dur"]) for s in found
            if all(k in s["args"] and s["args"][k] == v
                   for k, v in need.items()))
    total = sum(b - a for a, b in counted)
    for name in params.get("minus", ()):
        for s in readings.spans.named(name, readings.window):
            a, b = s["start"], s["start"] + s["dur"]
            if any(ca <= a and b <= cb for ca, cb in counted):
                total -= b - a
    return 100.0 * total / (hi - lo)

"""A registry count or sum over the window (or the whole process).

params: ``counter`` (family name), ``field`` (``value``, ``sum`` or
``count``; default ``value``), ``labels`` (optional), ``span``
(``window``, default, or ``process``), ``per_second`` (divide by the
window's length).
"""


def read(readings, params):
    from benchmark import harness

    field = params.get("field", "value")
    labels = params.get("labels", {})
    total = harness.metric_total(readings.after, params["counter"], field,
                                 **labels)
    if params.get("span", "window") == "window":
        total -= harness.metric_total(readings.before, params["counter"],
                                      field, **labels)
    if params.get("per_second"):
        total /= readings.window[1] - readings.window[0]
    return total

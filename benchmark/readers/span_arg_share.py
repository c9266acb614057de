"""The mean of a numeric argument of a host span, as a share (in %) of a
size the traffic file states: mean ``slots`` of ``decode.step`` over
``max_slots``.

params: ``span``, ``arg``, ``of`` (key of the traffic file).
"""


def read(readings, params):
    spans = [s for s in readings.spans.named(params["span"], readings.window)
             if params["arg"] in s["args"]]
    if not spans:
        return None
    mean = sum(float(s["args"][params["arg"]]) for s in spans) / len(spans)
    return 100.0 * mean / float(readings.cell.traffic[params["of"]])

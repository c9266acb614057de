"""One registry counter's growth over the window as a multiple of
another's: the table entries the attention kernel walked over those its
grid spans. Nothing to read where the denominator did not move.

params: ``counter``, ``over`` (family names), ``labels`` (optional, for
both), ``scale`` (multiplier, default 1; 100 for a share in %).
"""

from . import counter_delta


def read(readings, params):
    def grew(name):
        return counter_delta.read(readings, {
            "counter": name, "labels": params.get("labels", {})})

    over = grew(params["over"])
    if over <= 0:
        return None
    return float(params.get("scale", 1.0)) * grew(params["counter"]) / over

"""A counter the driver took over the window (deltas of public
attributes of ``engine.stats``, the driver's own counts), or the ratio
of two. Parameters: ``counter``, optionally ``over`` and ``scale``."""


def read(params: dict, ctx: dict):
    c = ctx["counters"]
    if params["counter"] not in c:
        return None
    value = float(c[params["counter"]])
    if "over" in params:
        if not c.get(params["over"]):
            return None
        value /= float(c[params["over"]])
    return value * params.get("scale", 1.0)

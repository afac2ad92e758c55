"""Device seconds of the events that match a prefix, per unit of work, on
the device that spent most in them:
{"kind": "trace_sum", "prefixes": ["hist_tiles"], "scale": 1}.
``scale`` converts seconds to the metric's unit (1000 for ms)."""


def read(spec: dict, ctx):
    if ctx.view is None or not ctx.units:
        return None
    total = ctx.view.sum_matching(spec["prefixes"])
    if total is None:
        return None
    return total / ctx.units * spec.get("scale", 1)

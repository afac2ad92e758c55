"""Device busy seconds (union of its operations' intervals, mean over the
devices) per unit of work, less the events of ``minus_prefixes``:
{"kind": "trace_busy", "minus_prefixes": ["hist_tiles"], "scale": 1}."""


def read(spec: dict, ctx):
    if ctx.view is None or not ctx.units:
        return None
    minus = ctx.view.sum_matching(spec.get("minus_prefixes", [])) or 0.0
    return (ctx.view.busy_s - minus) / ctx.units * spec.get("scale", 1)

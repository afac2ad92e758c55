"""A count the program kept over the window, per unit of work (iteration or
call): {"kind": "counter", "counter": "dispatches"}."""


def read(spec: dict, ctx):
    value = ctx.counters.get(spec["counter"])
    if value is None or not ctx.units:
        return None
    return value / ctx.units

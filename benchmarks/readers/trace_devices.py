"""How evenly the devices of one traced window were busy: the slowest
chip's busy seconds (``TraceView.busy``: the union of its operations'
intervals) over the mean of all devices':
{"kind": "trace_devices", "stat": "busy_max_over_mean"}. 1.0 where every
chip worked as long; under a learner that waits for the slowest chip at
every collective, what is above 1 is what the others idle. Nothing
without a trace, or where fewer than ``min_devices`` (default 2) device
planes were traced: one plane has nothing to be compared with."""


def read(spec: dict, ctx):
    if ctx.view is None:
        return None
    busy = list(ctx.view.busy.values())
    if not sum(busy):
        return None
    if spec["stat"] != "busy_max_over_mean":
        raise ValueError(f"trace_devices: no stat {spec['stat']!r}")
    return max(busy) / (sum(busy) / len(busy))

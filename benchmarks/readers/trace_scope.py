"""Device self-seconds, per unit of work, of the events whose HLO
instruction the program traced inside one of its ``jax.named_scope`` phases
(``lightgbm_tpu.utils.profiling.SCOPES``):

    {"kind": "trace_scope", "scopes": ["rung_gather"],
     "minus_prefixes": ["hist_tiles"]}
    {"kind": "trace_scope", "unscoped": true, "minus_prefixes": ["hist_tiles"]}

A device event is named by its instruction (``fusion.10``), which says
nothing about the phase and changes with every edit to the step. The join
goes through ``lightgbm_tpu.telemetry.scope_table(shapes=True)``: the
program's own table ``{hlo_module: {instruction: (scope, result shape)}}``.
The device's op events carry no module name (their only stats are device
offsets), so an instruction name that two programs share is told apart by
the result shape in ``Event.detail``.

Owned time, on the busiest device's clipped events: every instant of the
line's busy time goes to ONE event, the one that started last among those
running then (``owned_seconds``). For events that nest this is
``trace_reduce.self_times``: a ``while`` or ``cond`` does not swallow its
body. The chip's op line does not quite nest (a ``copy-done`` is still open
when the op after it starts; 0.88 s of a 14.55 s window, PERF.md section
3), and there ``self_times`` counts the overlap twice, while this partition
stays exact. Events that match ``minus_prefixes`` (the ``hist_tiles``
kernels, which have a metric of their own) are dropped after the
partition. ``unscoped`` sums what maps to no scope, events of programs the
table does not know included, so the scopes, ``unscoped`` and the dropped
kernels add up to the device's busy time. Nothing without a trace, or with
a program that has no such table (the parent of the PR that added it).
"""

from collections import defaultdict

import trace_reduce


def same_shape(shape: str, detail: str) -> bool:
    """Whether an event's text (``Event.detail``: result shape, opcode and
    operands, cut short) begins with this result shape."""
    if len(shape) >= len(detail):
        return shape.startswith(detail)
    return detail.startswith(shape + " ")


def scope_of_event(ev, table):
    """The scope of one event, or None: by instruction name and, where the
    event carries its text, by the result shape too, so that an event of a
    program the table does not hold takes no scope from an instruction of
    the same name in one it holds. None too where two programs' matching
    instructions disagree."""
    hits = [m[ev.name] for m in table.values() if ev.name in m]
    if ev.detail:
        hits = [h for h in hits if same_shape(h[1], ev.detail)]
    scopes = {scope for scope, _shape in hits}
    return scopes.pop() if len(scopes) == 1 else None


def owned_seconds(events):
    """[(event, seconds)]: the union of the events' intervals, cut so that
    each instant belongs to the event that started last among those running
    at it (of two that start together, the shorter)."""
    owned, stack, t = defaultdict(float), [], None
    order = sorted(range(len(events)),
                   key=lambda i: (events[i].start, -events[i].dur))

    def advance(until):
        """Hand out the time from ``t`` up to ``until``."""
        nonlocal t
        while stack and t < until:
            top = stack[-1]
            if events[top].end <= t:
                stack.pop()
                continue
            cut = min(events[top].end, until)
            owned[top] += cut - t
            t = cut
        t = until

    for i in order:
        if t is None:
            t = events[i].start
        advance(events[i].start)
        stack.append(i)
    if stack:
        advance(max(events[i].end for i in stack))
    return [(events[i], s) for i, s in owned.items()]


def instructions_by_scope(events, table, minus_prefixes=()):
    """{scope or None: {instruction and shape: owned seconds}} over one
    device's events, less those that match ``minus_prefixes``."""
    acc = defaultdict(lambda: defaultdict(float))
    for ev, t in owned_seconds(events):
        if not trace_reduce.matches(ev, minus_prefixes):
            acc[scope_of_event(ev, table)][
                f"{ev.name} {ev.detail}"[:60]] += t
    return acc


def seconds_by_scope(events, table, minus_prefixes=()):
    """{scope or None: owned seconds} over one device's events."""
    return {scope: sum(names.values()) for scope, names in
            instructions_by_scope(events, table, minus_prefixes).items()}


def _by_scope(ctx, minus_prefixes):
    """The reduction, once per traced run and list of prefixes."""
    cache = ctx.__dict__.setdefault("scope_seconds", {})
    key = tuple(minus_prefixes)
    if key not in cache:
        cache[key] = None
        try:
            from lightgbm_tpu import telemetry
            make = telemetry.scope_table
        except (ImportError, AttributeError):
            return None
        table = make(shapes=True)
        if not table:
            return None
        events = ctx.view.devices[ctx.view.busiest]
        top = instructions_by_scope(events, table, minus_prefixes)
        cache[key] = {scope: sum(names.values())
                      for scope, names in top.items()}
        busy = trace_reduce.union_seconds([(e.start, e.end) for e in events])
        nested = sum(t for _e, t in trace_reduce.self_times(events))
        ctx.log(f"trace_scope: {len(events)} events, busy {busy:.6f} s; "
                f"trace_reduce.self_times sums to {nested:.6f} s (more "
                f"where events overlap without nesting); instructions "
                f"scoped / all (0 scoped: an executable from a cache entry "
                f"that a build without scopes wrote) "
                f"{ {m: f'{sum(s is not None for s, _ in t.values())}/{len(t)}' for m, t in table.items()} }")
        for scope, names in sorted(top.items(),
                                   key=lambda kv: -cache[key][kv[0]]):
            ctx.log(f"trace_scope: {scope}={cache[key][scope]:.6f} s, less "
                    f"{list(minus_prefixes)}; heaviest: " + "; ".join(
                        f"{t:.6f} {n}" for n, t in sorted(
                            names.items(), key=lambda kv: -kv[1])[:6]))
    return cache[key]


def read(spec: dict, ctx):
    if ctx.view is None or not ctx.units:
        return None
    by = _by_scope(ctx, spec.get("minus_prefixes", []))
    if by is None:
        return None
    wanted = [None] if spec.get("unscoped") else spec["scopes"]
    return sum(by.get(s, 0.0) for s in wanted) / ctx.units \
        * spec.get("scale", 1)

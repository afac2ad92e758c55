"""Seconds and counts from the program's own timeline of host spans:

    {"kind": "timeline", "scope": "setup", "stat": "owned_s",
     "spans": ["construct"]}
    {"kind": "timeline", "scope": "setup", "stat": "total_s",
     "spans": ["bin_rows", "efb_place", "shard_place"]}
    {"kind": "timeline", "scope": "setup", "stat": "count",
     "spans": ["compile"],
     "where": {"stage": "backend", "outcome": ["miss", null]}}
    {"kind": "timeline", "scope": "window", "stat": "self_s",
     "spans": ["fused_dispatch", "score_dispatch"]}

``lightgbm_tpu.utils.profiling.timeline()`` is ``{"process_start_ns",
"setup": [...], "ring": [...]}``: every host span the library opened, as
``{id, parent, name, t0_ns, t1_ns, thread, attrs}`` on ``time.time_ns()``.
Nothing here reads anything else of the program.

The iterations of the job's training are the ``fused_dispatch`` spans of the
thread that opened the first one, in order: ``warmup_iterations`` of the
cell, then ``ctx.units`` in the window (whatever the job trains after that, a
probe tree, is not counted). An iteration runs from its ``fused_dispatch``'s
start to the next one's.

- scope ``setup``: [process start, the end of the ``callbacks`` span that
  follows the last warm-up iteration's dispatch]: where ``setup_s`` ends
  (the job opens its window inside that callback).
- scope ``window``: from the first window iteration's dispatch to the end of
  the ``callbacks`` span after the last one's; seconds and counts are
  divided by ``ctx.units``.

- ``owned_s``: every instant of the scope has ONE owner: the innermost
  open span (the one that began last) among ``compile``, ``plan``,
  ``construct`` and ``import``; else ``loop`` from the first
  ``fused_dispatch``'s start on; else ``unspanned``. The seconds whose owner
  is in ``spans``. The six owners sum to the scope.
- ``total_s``: the seconds of the scope that a span named in ``spans``
  covers, an instant counted once (a listed span inside a listed span adds
  nothing).
- ``self_s``: the listed spans' durations less their children's.
- ``count``: the listed spans that began in the scope.
- ``spans`` ``["*"]`` with ``total_s`` is every span of the training
  thread; ``"invert": true`` gives the scope less that.

``where`` keeps the spans whose ``attrs`` hold the given value, or one of a
list of values (``null`` is a missing or empty one). Nothing (None) where
the program has no ``profiling.timeline`` (the parent of the PR that added
it), no process start, or fewer iterations than the scope needs.
"""

OWNERS = ("compile", "plan", "construct", "import")


def _timeline():
    try:
        from lightgbm_tpu.utils import profiling
    except ImportError:
        return None
    fn = getattr(profiling, "timeline", None)
    return fn() if fn is not None else None


def union_ns(spans, t0: int, t1: int) -> int:
    total, end = 0, t0
    for a, b in sorted((max(s["t0_ns"], t0), min(s["t1_ns"], t1))
                       for s in spans):
        if b > end:
            total += b - max(a, end)
            end = b
    return total


def owned_ns(spans, t0: int, t1: int, loop_from) -> dict:
    """{owner: ns} over [t0, t1], summing to t1 - t0."""
    own = [s for s in spans if s["name"] in OWNERS
           and s["t1_ns"] > t0 and s["t0_ns"] < t1]
    cuts = {t0, t1}
    if loop_from is not None and t0 < loop_from < t1:
        cuts.add(loop_from)
    for s in own:
        cuts.update((max(s["t0_ns"], t0), min(s["t1_ns"], t1)))
    out = dict.fromkeys(OWNERS + ("loop", "unspanned"), 0)
    cuts = sorted(cuts)
    for a, b in zip(cuts, cuts[1:]):
        open_ = [s for s in own if s["t0_ns"] <= a and s["t1_ns"] >= b]
        if open_:
            name = max(open_, key=lambda s: s["t0_ns"])["name"]
        elif loop_from is not None and a >= loop_from:
            name = "loop"
        else:
            name = "unspanned"
        out[name] += b - a
    return out


def self_ns(spans, chosen) -> int:
    """The chosen spans' durations less those of their children."""
    inside = {}
    for s in spans:
        inside[s["parent"]] = inside.get(s["parent"], 0) \
            + s["t1_ns"] - s["t0_ns"]
    return sum(max(s["t1_ns"] - s["t0_ns"] - inside.get(s["id"], 0), 0)
               for s in chosen)


def _matches(span, where: dict) -> bool:
    for key, want in where.items():
        have = span["attrs"].get(key)
        if have not in (want if isinstance(want, list) else [want]):
            return False
    return True


def scope_of(spans, scope: str, warm: int, units: int, start):
    """(t0, t1, the training thread, the first dispatch's start) or None."""
    disp = sorted((s for s in spans if s["name"] == "fused_dispatch"),
                  key=lambda s: s["t0_ns"])
    if not disp:
        return None
    thread = disp[0]["thread"]
    disp = [s for s in disp if s["thread"] == thread]
    need = warm if scope == "setup" else warm + units
    if len(disp) < max(need, 1) or (scope == "setup" and start is None):
        return None
    last = disp[need - 1]
    after = [s["t1_ns"] for s in spans
             if s["name"] == "callbacks" and s["thread"] == thread
             and s["t0_ns"] >= last["t0_ns"]]
    if not after:
        return None
    t0 = start if scope == "setup" else disp[warm]["t0_ns"]
    return t0, min(after), thread, disp[0]["t0_ns"]


def read(spec: dict, ctx):
    tl = _timeline()
    if tl is None:
        return None
    spans = list(tl["setup"]) + list(tl["ring"])
    scope = spec["scope"]
    found = scope_of(spans, scope, int(ctx.cell["warmup_iterations"]),
                     int(ctx.units), tl.get("process_start_ns"))
    if found is None:
        return None
    t0, t1, thread, loop_from = found
    per = 1.0 if scope == "setup" else 1.0 / max(int(ctx.units), 1)
    names, stat = spec["spans"], spec["stat"]
    if stat == "owned_s":
        owned = owned_ns(spans, t0, t1, loop_from)
        return sum(owned[n] for n in names) * 1e-9 * per
    where = spec.get("where", {})
    if names == ["*"]:
        chosen = [s for s in spans if s["thread"] == thread]
    else:
        chosen = [s for s in spans if s["name"] in names]
    chosen = [s for s in chosen if _matches(s, where)
              and s["t1_ns"] > t0 and s["t0_ns"] < t1]
    if stat == "total_s":
        ns = union_ns(chosen, t0, t1)
        if spec.get("invert"):
            ns = t1 - t0 - ns
        return ns * 1e-9 * per
    began = [s for s in chosen if s["t0_ns"] >= t0]
    if stat == "self_s":
        return self_ns(spans, began) * 1e-9 * per
    if stat == "count":
        return len(began) * per
    raise ValueError(f"timeline reader: unknown stat {stat!r}")

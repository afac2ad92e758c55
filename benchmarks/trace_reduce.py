"""From a profiler trace to numbers: two reductions and a loader.

The reductions are pure functions of event lists, so benchmarks/tests can
check them on a hand-made list:

- ``union_seconds``: the length of the union of intervals (device busy).
- ``sum_matching``: the summed duration of events whose name starts with,
  or whose stats mention, one of a list of prefixes (a kernel's time).

Beside them: ``self_times`` (an op's duration minus the ops nested in it,
so that a ``while`` does not swallow its body in the by-name breakdown) and
``idle_gaps`` (the complement of busy, each gap labelled by what the host
was doing at its middle).

``load`` reads an ``.xplane.pb`` with ``jax.profiler.ProfileData``: device
planes are those named ``/device:TPU:<n>``; of their lines the one named
``XLA Ops`` holds one event per executed HLO instruction. Off the chip (the
CPU rehearsal) there is no device plane; host events that carry an
``hlo_op`` stat then stand in as one pseudo device so the same code runs.

``python trace_reduce.py <file.xplane.pb>`` prints what a trace holds
(planes, lines, the heaviest names with their stats): look at one real
trace by hand before writing a reader against it.
"""

import dataclasses
import glob
import os
import re
import sys
from collections import defaultdict

DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
# names the benchmark's jobs put around each unit of work
# (jax.profiler.TraceAnnotation): the traced window is their span
ANNOTATION_PREFIX = "bench_"
DETAIL_CHARS = 72
# gaps between back-to-back operations are not the host's doing: they are
# summed under one label instead of being looked up one by one
MIN_LABELLED_GAP = 10e-6
SHORT_GAPS = "gaps_under_10us_between_operations"


@dataclasses.dataclass
class Event:
    name: str             # an HLO instruction's own name: "fusion.10"
    start: float          # seconds on the trace's clock
    dur: float            # seconds
    stats: tuple = ()     # the event's string stats, for matching
    detail: str = ""      # output shape and opcode, for the breakdown

    @property
    def end(self) -> float:
        return self.start + self.dur


@dataclasses.dataclass
class Trace:
    devices: dict         # plane name -> [Event] (the ops line)
    host: list            # [Event] of the thread that holds the annotations
    annotations: list     # [Event] whose name starts with ANNOTATION_PREFIX


# ------------------------------------------------------------- reductions
def union_seconds(intervals) -> float:
    """Length of the union of (start, end) intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        elif e > cur_e:
            cur_e = e
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def matches(ev: Event, prefixes) -> bool:
    """An event belongs to a prefix by its name, or by a string stat (a
    kernel whose instruction is named ``custom-call.7`` carries its own
    name there)."""
    return any(ev.name.startswith(p) or any(v.startswith(p) for v in ev.stats)
               for p in prefixes)


def sum_matching(events, prefixes) -> float:
    """Summed duration of the events that match a prefix."""
    return sum(ev.dur for ev in events if matches(ev, prefixes))


def self_times(events):
    """[(event, self seconds)]: duration minus the events nested inside it
    on the same line (a parent starts no later and ends no earlier)."""
    out, stack = [], []
    for ev in sorted(events, key=lambda e: (e.start, -e.dur)):
        while stack and ev.start >= stack[-1][0].end:
            out.append(tuple(stack.pop()))
        if stack:
            stack[-1][1] -= ev.dur
        stack.append([ev, ev.dur])
    out.extend(tuple(s) for s in stack)
    return [(ev, max(t, 0.0)) for ev, t in out]


def by_name(events, top: int = 10):
    """[[name, self seconds]] of the heaviest names."""
    acc = defaultdict(float)
    for ev, t in self_times(events):
        acc[f"{ev.name} {ev.detail}".strip()] += t
    return [[n, t] for n, t in sorted(acc.items(), key=lambda x: -x[1])[:top]]


def idle_gaps(intervals, window, host, annotations, top: int = 10):
    """[[label, seconds]]: idle time inside ``window`` = (start, end),
    summed by what the host was doing at each gap's middle: the benchmark's
    annotation there, then the innermost host event of that thread."""
    w0, w1 = window
    gaps, cur = [], w0
    for s, e in sorted(intervals):
        if s > cur:
            gaps.append((cur, min(s, w1)))
        cur = max(cur, e)
        if cur >= w1:
            break
    if cur < w1:
        gaps.append((cur, w1))
    host = [e for e in host if not e.name.startswith(ANNOTATION_PREFIX)]
    acc = defaultdict(float)
    for s, e in gaps:
        if e - s >= MIN_LABELLED_GAP:
            acc[_label((s + e) / 2, annotations, host)] += e - s
        elif e > s:
            acc[SHORT_GAPS] += e - s
    return [[n, t] for n, t in sorted(acc.items(), key=lambda x: -x[1])[:top]]


def _innermost(t: float, events):
    best = None
    for ev in events:
        if ev.start <= t < ev.end and (best is None or ev.dur < best.dur):
            best = ev
    return best


def _label(t: float, annotations, host) -> str:
    ann = _innermost(t, annotations)
    inner = _innermost(t, host)
    parts = [ann.name if ann else "outside_annotations"]
    if inner is not None and (ann is None or inner.dur <= ann.dur):
        parts.append(inner.name)
    return "/".join(parts)[:120]


# ----------------------------------------------------------------- loading
def find_xplane(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def split_name(raw: str):
    """The device's op line names an event by the whole HLO instruction,
    ``%fusion.10 = u8[5250048,28]{0,1:T(8,128)} fusion(u8[...] %arg), ...``:
    -> ("fusion.10", "u8[5250048,28] fusion(u8[...] %arg), ...") with the
    layouts taken out and the rest cut short."""
    if raw.startswith("%") and " = " in raw:
        name, rest = raw[1:].split(" = ", 1)
        return name, re.sub(r"\{[^{}]*\}", "", rest)[:DETAIL_CHARS]
    return raw, ""


def _event(ev) -> Event:
    name, detail = split_name(ev.name)
    stats = tuple(v for _k, v in ev.stats if isinstance(v, str))
    return Event(name, ev.start_ns * 1e-9, ev.duration_ns * 1e-9, stats,
                 detail)


def load(path: str) -> Trace:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices, host_lines = {}, []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices[plane.name] = [_event(e) for e in line.events]
        elif plane.name == HOST_PLANE:
            host_lines = [(ln.name, list(ln.events)) for ln in plane.lines]
    host, annotations = [], []
    for _name, evs in host_lines:
        ann = [_event(e) for e in evs
               if e.name.startswith(ANNOTATION_PREFIX)]
        if ann:
            annotations = ann
            host = [_event(e) for e in evs]
            break
    if not devices:
        pseudo = [_event(e) for _n, evs in host_lines for e in evs
                  if any(k == "hlo_op" for k, _v in e.stats)]
        if pseudo:
            devices["host-xla (no device plane)"] = pseudo
    return Trace(devices, host, annotations)


def window_of(trace: Trace):
    """(start, end) of the benchmark's annotations, or of the device events
    when the trace holds none."""
    evs = trace.annotations or [e for d in trace.devices.values() for e in d]
    if not evs:
        return None
    return min(e.start for e in evs), max(e.end for e in evs)


def clip(events, window):
    """The events' parts that lie inside the window."""
    w0, w1 = window
    out = []
    for ev in events:
        s, e = max(ev.start, w0), min(ev.end, w1)
        if e > s:
            out.append(dataclasses.replace(ev, start=s, dur=e - s))
    return out


class TraceView:
    """One loaded trace, reduced as the readers need it."""

    def __init__(self, trace: Trace):
        self.trace = trace
        self.window = window_of(trace)
        self.devices = {name: clip(evs, self.window)
                        for name, evs in trace.devices.items()}
        self.busy = {name: union_seconds(
            [(e.start, e.end) for e in evs])
            for name, evs in self.devices.items()}

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    @property
    def busy_s(self) -> float:
        """Mean over the devices used."""
        return sum(self.busy.values()) / len(self.busy)

    @property
    def busiest(self) -> str:
        return max(self.busy, key=self.busy.get)

    def sum_matching(self, prefixes):
        """Seconds of matching events on the device that spent most in
        them, or None where no device ran one."""
        best = max(sum_matching(evs, prefixes)
                   for evs in self.devices.values())
        return best if best > 0 else None

    def breakdown(self) -> dict:
        evs = self.devices[self.busiest]
        return {"device_ops": by_name(evs),
                "idle_gaps": idle_gaps(
                    [(e.start, e.end) for e in evs], self.window,
                    self.trace.host, self.trace.annotations)}



def describe(path: str, top: int = 40) -> str:
    """What a trace file holds, for reading by hand."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    lines = []
    for plane in data.planes:
        lines.append(f"PLANE {plane.name}")
        for line in plane.lines:
            evs = list(line.events)
            if not evs:
                continue
            t0 = min(e.start_ns for e in evs) * 1e-9
            t1 = max(e.start_ns + e.duration_ns for e in evs) * 1e-9
            lines.append(f"  LINE {line.name!r} events={len(evs)} "
                         f"from={t0:.6f} to={t1:.6f}")
            acc, cnt, sample = defaultdict(float), defaultdict(int), {}
            for e in evs:
                acc[e.name] += e.duration_ns * 1e-9
                cnt[e.name] += 1
                sample.setdefault(e.name, e)
            for n, t in sorted(acc.items(), key=lambda x: -x[1])[:top]:
                stats = {k: (v if not isinstance(v, str) else v[:160])
                         for k, v in sample[n].stats}
                lines.append(f"    {t:10.6f} s x{cnt[n]:<7d} {n[:100]!r} "
                             f"{stats}")
    return "\n".join(lines)


if __name__ == "__main__":
    target = sys.argv[1]
    if os.path.isdir(target):
        target = find_xplane(target)
    print(describe(target))

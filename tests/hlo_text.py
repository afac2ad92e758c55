"""Reading a compiled program's text (``compiled.as_text()``) by
computation, for the tests that ask where the compiler put an operation:
tests/test_expo_sparse.py (the CPU's text) and tests/test_chip_compile.py
(the described TPU's)."""

import re

from lightgbm_tpu import telemetry

_CALLED_RE = re.compile(
    r"\b(?:calls|to_apply|body|condition|true_computation|"
    r"false_computation)=%?([^\s,)}]+)")
_BRANCHES_RE = re.compile(r"\bbranch_computations=\{([^}]*)\}")


def computations(text):
    """{computation: its instruction lines}."""
    comps, comp = {}, None
    for ln in text.splitlines():
        if comp is None:
            m = telemetry._HLO_COMPUTATION_RE.match(ln)
            if m:
                comp = comps.setdefault(m.group(1), [])
        elif ln.startswith("}"):
            comp = None
        else:
            comp.append(ln)
    return comps


def _called(line):
    names = _CALLED_RE.findall(line)
    for group in _BRANCHES_RE.findall(line):
        names += [b.strip().lstrip("%") for b in group.split(",")]
    return names


def closure(comps, name):
    """The lines of a computation and of every computation it calls:
    fusions, loops and branches, to any depth."""
    out, todo, seen = [], [name], set()
    while todo:
        c = todo.pop()
        if c in seen:
            continue
        seen.add(c)
        for ln in comps[c]:
            out.append(ln)
            todo += _called(ln)
    return out


def route_branches(text):
    """``(dense, stream)``: the lines of the two branches of the
    conditional that ``grower._apply_split``'s routing traces where the
    data set has stream columns: the innermost conditional under scope
    ``apply_split`` of which exactly one branch holds the stream's
    scatter."""
    comps = computations(text)
    found = []
    for ln in text.splitlines():
        if " conditional(" not in ln or 'apply_split/cond"' not in ln:
            continue
        branches = [closure(comps, b) for b in _called(ln)]
        stream = [b for b in branches
                  if any("sparse_route/scatter" in x for x in b)]
        if len(branches) == 2 and len(stream) == 1 \
                and not any(" conditional(" in x for x in stream[0]):
            dense, = [b for b in branches if b is not stream[0]]
            found.append((dense, stream[0]))
    assert len(found) == 1, len(found)
    return found[0]

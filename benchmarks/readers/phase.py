"""Seconds of a set-up phase the job timed with the host clock:
{"kind": "phase", "phase": "tune"}. Nothing where the job has no such
phase."""


def read(spec: dict, ctx):
    return ctx.phases.get(spec["phase"])

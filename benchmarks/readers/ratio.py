"""One count the program kept over another:
{"kind": "ratio", "numerator": "rank_padded_slots",
 "denominator": "rank_documents"}. Nothing where the job filled neither
(a program without the counters)."""


def read(spec: dict, ctx):
    num = ctx.counters.get(spec["numerator"])
    den = ctx.counters.get(spec["denominator"])
    if num is None or not den:
        return None
    return num / den

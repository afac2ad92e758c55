"""Seconds the program itself counted before its first steady iteration:

    {"kind": "program_seconds", "source": "compile_stats",
     "keys": ["trace_s", "lower_s"],
     "program_prefixes": ["jit(_fused_step)", "jit(_fused_block)"]}
    {"kind": "program_seconds", "source": "compile_stats",
     "keys": ["backend_s"]}
    {"kind": "program_seconds", "source": "autotune_report",
     "keys": ["total_s"]}

``compile_stats`` is ``lightgbm_tpu.compile_cache.compile_stats()``: per
program (jax's name for it, ``jit(<function>)``) the seconds of Python
tracing, of lowering to an MLIR module and of the backend compile request
(an XLA build or a persistent-cache load), from ``jax.monitoring``'s
duration events. ``program_prefixes`` keeps the programs whose name starts
with one of them; without it all are summed. ``autotune_report`` is
``lightgbm_tpu.ops.pallas_hist.autotune_report()``. Nothing where the
program keeps no such key (the parent of the PR that added them).
"""


def _source(name: str):
    try:
        if name == "autotune_report":
            from lightgbm_tpu.ops import pallas_hist
            return pallas_hist.autotune_report()
        from lightgbm_tpu import compile_cache
        return compile_cache.compile_stats()
    except (ImportError, AttributeError):
        return None


def read(spec: dict, ctx):
    stats = _source(spec["source"])
    if stats is None:
        return None
    prefixes = tuple(spec.get("program_prefixes", ()))
    total = 0.0
    for key in spec["keys"]:
        value = stats.get(key)
        if value is None:
            return None
        if isinstance(value, dict):
            value = sum(v for name, v in value.items()
                        if not prefixes or name.startswith(prefixes))
        total += value
    return total * spec.get("scale", 1)

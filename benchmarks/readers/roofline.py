"""A kernel's share of its roofline, in percent: the least time the chip
could take for the work the program reports it executed (the larger of
operations over peak and bytes over peak bandwidth; kernel_ops.py computes
both from shapes, peaks.json holds the peaks) over the kernel's time in the
trace: {"kind": "roofline", "prefixes": ["hist_tiles"], "ops": "hist_tiles"}.
``ops_module`` names another module of this directory than kernel_ops, so
that a new kernel's arithmetic can come as a new file.
"""

import importlib


def read(spec: dict, ctx):
    if ctx.view is None or ctx.peaks is None or not ctx.work:
        return None
    seconds = ctx.view.sum_matching(spec["prefixes"])
    if seconds is None:
        return None
    module = importlib.import_module(spec.get("ops_module", "kernel_ops"))
    ops, byts, peak_key = getattr(module, spec["ops"])(ctx.work)
    t_ops = ops / ctx.peaks[peak_key]
    t_bytes = byts / ctx.peaks["hbm_bytes_per_s"]
    ctx.log(f"roofline[{spec['ops']}]: kernel={seconds:.6f} s "
            f"operations={ops:.4e} ({t_ops:.6f} s at {peak_key}) "
            f"bytes={byts:.4e} ({t_bytes:.6f} s) bound_by="
            f"{'operations' if t_ops >= t_bytes else 'bytes'}")
    return 100.0 * max(t_ops, t_bytes) / seconds

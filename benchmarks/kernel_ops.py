"""Operations and bytes a kernel's executed work needs, from shapes alone.

The yardstick for every ``<kernel>_roofline`` metric: a function here takes
the work the program reports it executed (``work``, filled by the job from
the program's counters) and returns ``(operations, bytes, peak_key)``;
``peak_key`` names the row of peaks.json the operations run against.
"""

LANES = 128          # MXU output lanes one histogram contraction fills
# histogram_method -> (MXU passes per contraction, peak the passes run at):
# the hilo modes split f32 statistics into two bf16 planes (two passes),
# q8 contracts int8 once
MODES = {"pallas_hilo": (2, "bf16_flops"), "pallas": (3, "bf16_flops"),
         "pallas_q8": (1, "int8_ops")}


def hist_tiles(work: dict):
    """The one-hot histogram contraction (ops/pallas_hist.py hist_tiles_*):
    every streamed row is contracted, per feature, against a one-hot of its
    bin over all ``bins`` columns into 128 output lanes: 2 x rows x features
    x bins x 128 operations a pass. Bytes: the row's bins (1 byte a feature)
    and its statistics row as the kernel lays it out (128 lanes of f32)."""
    passes, peak_key = MODES[work["histogram_method"]]
    rows = float(work["rows_streamed"])
    ops = 2.0 * rows * work["features"] * work["bins"] * LANES * passes
    byts = rows * (work["features"] + LANES * 4)
    return ops, byts, peak_key

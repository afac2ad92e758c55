"""Pallas TPU kernels for the histogram tile pass — the primary TPU path.

The fused re-design of the CUDA histogram kernels (reference:
src/treelearner/kernels/histogram_16_64_256.cu:16-120 — per-workgroup
shared-memory sub-histograms with atomic adds). On TPU there are no atomics;
instead each grid step builds the per-feature bin one-hot IN VMEM and
contracts it with the (leaf-slot x stat) channel matrix on the MXU,
accumulating into a VMEM-resident output that is flushed once.

Which operand sits where. The bin matrix is feature-major, ``[F, N]``: a
row block arrives with the rows on the LANES. The one-hot of a feature is
built in that same orientation, ``[bins (sublanes), rows (lanes)]``: the
feature's bin row is compared against an iota that runs down the sublanes,
so the bin row is only replicated, never moved from lanes to sublanes. The
channel matrix is ``[rows (sublanes), channels (lanes)]``, the statistics'
own orientation. The contraction is then a plain ``[M, K] x [K, N]``
matmul over the rows, one-hot as the lhs exactly as built (a rows-major
one-hot has to go through the transpose unit first, whole, for every
feature), and its result ``[bins, channels]`` is the accumulator's layout.

The grid, and which operand is blocked how. Up to ONE_BLOCK_FEATURES
device columns a launch is a grid over ROW blocks alone: the bins block
``[F, C]`` holds every feature of C rows, and the accumulator
``[F * _bin_rows(B), 128]`` stays in VMEM for the whole pass. The body is
unrolled over its features, so its compile time and that accumulator grow
with the width (5 s at 28 columns, 20 at 137; 262 MB of accumulator at
2,000). A wider matrix is therefore walked on a grid ``(feature blocks, row
blocks)``, the rows innermost: one feature block at a time takes the whole
sweep over the rows, and everything a feature owns is blocked with it, the
bins ``[fb, C]``, the accumulator and the emitted plane
``[fb * _bin_rows(B), 128]`` (zeroed at the block's first row step), and in
the epilogue form the parent planes, the scan metadata ``fm [fb, 4]`` in
SMEM and the candidate tables ``[fb, 2, 16, 128]``, whose scan runs at the
block's last row step. What belongs to the ROWS is not blocked: the leaf
ids, the statistics and the lane table are read again, and the rhs built
again, for every feature block; that is the implementation's cost (4% of a
pass at blocks of 200 columns), not the roofline's work. Every
block runs the same compiled body: the width is padded to whole blocks in
XLA (all-zero bin columns, beside the row pad that is made anyway) and the
padding's planes and candidates are cut off again. ``feature_block`` gives
the width, a pure function of the shape.

What the kernels fuse:

1. **In-kernel leaf channels.** The (leaf-onehot x stats) RHS is built
   inside the grid step from the raw ``[N]`` leaf ids and ``[N, S]`` stats.
   The previous design prepared an ``[N, 128]`` f32 RHS in XLA — ~18x the
   HBM bytes of the int8 bin matrix it accompanied (25x+ in the hilo mode's
   bf16-pair form), written and re-read every pass. Fused, the RHS never
   exists outside VMEM: per-pass traffic drops to
   ``bins + stats + leaf_ids + output``.

2. **Quantized-gradient mode.** ``mode="q8"`` contracts int8 stats with the
   int8 one-hot on the MXU's int8 path (~2x the bf16 rate) with EXACT int32
   accumulation; the grower rescales to f32 once per tile, at split-gain
   time (models/grower.py quant8). ``Config.quantized_grad`` turns this
   into an end-to-end training mode: int8 grad/hess with stochastic
   rounding, following the XGBoost-GPU recipe (arXiv:1706.08359 §5).

3. **Split-finding epilogue** (second half of this file): the last grid
   step scans the accumulated planes for the best split per (leaf,
   feature) in VMEM.

The compaction ladder's rungs (ops/histogram.py compact_indices) reach
these kernels as COMPACTED COPIES that XLA gathers from the row-major bin
matrix (ops/histogram.py histogram_tiles). An in-kernel row gather — one
DMA per pending row from the HBM-resident arrays — was part of this file
until the kernels first met the TPU compiler: Mosaic slices an HBM operand
at tile granularity only (8 sublanes x 128 lanes of 32-bit words), so
neither one bin column of the feature-major matrix nor one 28-byte row of
the row-major one is a legal DMA source.

Two float precision modes share the same kernel body (``mode``):

- "hilo" (the fast default): the RHS is split into [hi || lo] bf16 halves
  of the f32 channels IN KERNEL; both halves' products accumulate in f32 on
  the MXU, so the recombined sum carries ~16-17 mantissa bits of input
  precision (~2^-17 relative rounding) with exact counts — comparable to
  (slightly coarser than) the reference GPU's float32 histograms
  (gpu_use_dp=false, docs/GPU-Performance.rst:133-140), at 2 bf16 MXU
  passes.
- "highest": f32 RHS contracted at Precision.HIGHEST (6 bf16 passes) — the
  precise alternative, selected by ``deterministic=true``.

``interpret=True`` runs any kernel through the Pallas interpreter so the
whole pipeline is testable on CPU hosts (``Config.hist_pallas_interpret``);
tier-1 parity suites run this way, and tests/test_chip_compile.py hands
the same kernels to the TPU compiler at the Higgs width.
"""

from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_PAD = 128          # lane width; P*S channels are padded up to this

# kernel names (suffixed with the mode): how a device trace and a lowered
# program's text name the two kernel forms
KERNEL_NAME = "hist_tiles"
EPILOGUE_KERNEL_NAME = "hist_tiles_split_epilogue"


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def _bin_rows(b: int) -> int:
    """Rows each feature occupies in the kernels' VMEM planes: the bin
    count rounded up to the 8-sublane tile, so every feature's slab starts
    tile-aligned (the epilogue loads slabs at a dynamic feature index)."""
    return _round_up(b, 8)


def _chan_layout(p: int, s: int):
    """Static per-lane channel layout: output lane q carries stat channel
    ``s_of_q[q]`` of tile slot ``p_of_q[q]`` (q < p*s; higher lanes are
    dead padding). Matches the ``reshape(-1, p*s)`` layout of the XLA
    formulations so outputs slice/reshape identically."""
    q = np.arange(_PAD)
    valid = q < p * s
    p_of_q = np.where(valid, np.minimum(q // s, p - 1), 0)
    s_of_q = np.where(valid, q % s, 0)
    return p_of_q, s_of_q, valid


def chan_leaf_table(sel: jax.Array, s: int) -> jax.Array:
    """[1, _PAD] int32: the leaf id each output lane accumulates, or -9 for
    dead lanes. Built in XLA from the tile selection ``sel`` (tiny — P
    int32 values), consumed whole by every grid step."""
    p = sel.shape[0]
    p_of_q, _, valid = _chan_layout(p, s)
    return jnp.where(jnp.asarray(valid),
                     sel[jnp.asarray(p_of_q)], jnp.int32(-9))[None, :]


def split_hilo(rhs: jax.Array) -> jax.Array:
    """f32 [N, W] -> [hi || lo] bf16 [N, 2W]: the two halves' exact-product
    contributions recombine to ~16-17 mantissa bits of input precision."""
    rhs_hi = rhs.astype(jnp.bfloat16)
    rhs_lo = (rhs - rhs_hi.astype(jnp.float32)).astype(jnp.bfloat16)
    return jnp.concatenate([rhs_hi, rhs_lo], axis=1)


def _accumulate(binsT_blk, leaf_blk, stats_blk, chan_leaf, out_ref,
                *, f, b, c, s, mode):
    """Shared fused compute body: build the leaf-channel RHS and the packed
    bin one-hot for one row block entirely in VMEM and contract on the MXU.

    One-hot [bins, C] times RHS [C, channels]: the module docstring says
    which operand sits where, and why.

    binsT_blk: [F, C] int8 bin columns for this block's rows.
    leaf_blk:  [C] int32 leaf slot per row.
    stats_blk: [C, S] f32 (or int8 for q8) per-row statistics.
    chan_leaf: [_PAD] int32 leaf id per output lane (-9 = dead lane).
    out_ref:   [F * _bin_rows(B), _PAD] accumulator; feature j's bins are
               rows [j * _bin_rows(B), j * _bin_rows(B) + B).
    """
    # --- leaf-channel RHS [C, _PAD]: lane q carries stats[:, q mod S]
    # where the row's leaf id matches the lane's slot, else 0. The layout
    # is periodic, so the expansion is a static tile+slice (no gather, no
    # captured index constants — both would fail kernel tracing).
    reps = -(-_PAD // max(s, 1))
    stat_chan = jnp.concatenate([stats_blk] * reps, axis=1)[:, :_PAD]
    # lanes q >= P*S carry garbage stat values here; their chan_leaf is -9
    # so ``match`` zeroes them below
    match = leaf_blk[:, None] == chan_leaf[None, :]          # [C, _PAD]
    oh_dtype = {"hilo": jnp.bfloat16, "highest": jnp.float32,
                "q8": jnp.int8}[mode]
    acc_dtype = jnp.int32 if mode == "q8" else jnp.float32
    prec = jax.lax.Precision.HIGHEST if mode == "highest" else None
    if mode == "q8":
        rhs = jnp.where(match, stat_chan, jnp.int8(0))
    else:
        rhs = jnp.where(match, stat_chan.astype(jnp.float32),
                        jnp.float32(0.0))
        if mode == "hilo":
            rhs = split_hilo(rhs)                            # [C, 2*_PAD]
    # Feature packing: with b <= 64 bins a single feature's one-hot fills
    # only b of the MXU's 128 output rows, so the matmul runs at b/128
    # utilization. Pack g = 128//b features one below the other into one
    # [g*b, C] one-hot (disjoint sublane ranges, so a plain sum builds the
    # OR) — the max_bin=63 configuration then drives full 128-row MXU
    # tiles instead of half-empty ones.
    g = max(1, _PAD // b) if b <= _PAD else 1
    bp = _bin_rows(b)
    for j0 in range(0, f, g):                                # static unroll
        m = min(g, f - j0)
        iota = jax.lax.broadcasted_iota(jnp.int32, (m * b, c), 0)
        oh = None
        for k in range(m):
            col = binsT_blk[j0 + k:j0 + k + 1, :].astype(jnp.int32) + k * b
            hit = (iota == col).astype(oh_dtype)             # [m*B, C]
            oh = hit if oh is None else oh + hit
        acc = jax.lax.dot_general(
            oh, rhs, (((1,), (0,)), ((), ())), precision=prec,
            preferred_element_type=acc_dtype)
        if mode == "hilo":
            acc = acc[:, :_PAD] + acc[:, _PAD:]              # recombine
        for k in range(m):
            r0 = (j0 + k) * bp
            out_ref[r0:r0 + b, :] += acc[k * b:(k + 1) * b, :]


# rows one unrolled _accumulate body covers. The body is straight-line
# code whose size — and Mosaic's time to schedule it, and its VMEM need —
# grows with its row count (PERF.md, Findings, holds the readings). A row
# block larger than this is therefore walked in chunks by a loop, which
# keeps every block size at the small body's compile cost and VMEM need.
_CHUNK = 1024


# ------------------------------------------------------------ kernel shape

# Rows a kernel launch takes per grid step where the configuration names no
# ``hist_block``: the one default of both learners, of the kernels'
# signatures and of the OOM ladder's shrink. A constant, not a
# measurement: every block of _CHUNK rows or more walks the same
# _CHUNK-row body. On a TPU v5 lite, 1024 / 2048 / 4096 / 8192 rows read
# 3.574 / 3.571 / 3.570 / 3.570 s an iteration at 10.5M x 28 and 2.963 /
# 2.918 / 2.859 / 2.858 at 2.27M x 137 (PERF.md, PR 31): 4096 is within
# 0.04% of the best at both widths, 2048 costs 2.1% at the wide one.
DEFAULT_BLOCK = 4096


def oom_shrink_block(block: int) -> int:
    """Rung 1 of the OOM degradation ladder: a histogram row block a
    quarter the current size (floor 256 — below that the per-pass
    overheads dominate and rung 2's formulation change is the right
    lever). ``block=0`` (no block named) shrinks from DEFAULT_BLOCK."""
    return max(256, (block or DEFAULT_BLOCK) // 4)


def structural_tile_leaves(stats_channels: int = 3) -> int:
    """The leaf batch the kernel wants, by construction: the widest tile
    whose (leaf x stat) channels fit one 128-lane group. No measurement
    needed — kernel cost is flat in the tile width (channels occupy the
    full lane group either way)."""
    return max(1, _PAD // max(stats_channels, 1))


# Features one kernel body covers. The body is unrolled over its features
# (_accumulate) and its accumulator, an f32 [features * _bin_rows(B), 128]
# plane, lives whole in VMEM, so compile time and VMEM grow with the
# width. Constants, not measurements of a run, as DEFAULT_BLOCK is:
#
# ONE_BLOCK_FEATURES: up to this many device columns a launch is ONE block
# with a grid over the row blocks alone, the kernel as it was before there
# were feature blocks (its parent and emitted planes double-buffered: five
# planes of 144 x 131 KB are 94 MB of the 100 MB limit, and the TPU
# compiler takes it for a described v5e in both forms, ``hilo`` and ``q8``).
# Every width a benchmark cell had before `epsilon.train` (8, 28, 68, 137)
# is under it and keeps its program, operation for operation.
#
# MAX_FEATURE_BLOCK: the widest block of a wider matrix, walked block by
# block on a second, outer grid axis, every block the same compiled body.
# What a body pays a row whatever its columns (the rhs build, the
# statistics' 128 lanes: 5.6 ns, ops/histogram.py RUNG_COSTS) it pays once
# a block, so wide blocks win: at 400,000 x 2,000, 255 bins, ``hilo`` on a
# TPU v5 lite a pass of the epilogue / the plain form read 0.6496 / 0.6017
# s at 80 columns a block (25 blocks), 0.6531 / 0.6247 at 128 (16 blocks,
# 48 padding columns), 0.6272 / 0.5998 at 160 (13, 80 padding) and 0.6035
# / 0.5712 at 200 (10 blocks, none: 87.9% / 92.8% of the bf16 roofline;
# the fit 5.6 x blocks + 0.69 x columns ns a row holds within 1%); one
# launch compiled cold in 57 / 60 / 68 / 75 s on the chip's host (18 / 22
# / 28 / 36 s on a faster one). 200 is also where VMEM ends: with the
# block's parent and emitted planes single-buffered (_fused_epi_call) the
# epilogue form holds three planes of 26 MB; 224 columns do not compile,
# nor do 160 with those planes double-buffered (PERF.md, PR 39).
ONE_BLOCK_FEATURES = 144
MAX_FEATURE_BLOCK = 200


def feature_block(f: int, num_bins: int, mode: str = "hilo",
                  epilogue: bool = False) -> int:
    """Features a kernel body covers at ``f`` device columns: ``f`` itself
    (one block) up to ONE_BLOCK_FEATURES, else the narrowest multiple of 8
    (the bin block's sublane tile) that walks the matrix in as few blocks
    as MAX_FEATURE_BLOCK allows (two at least), so the last block is as
    full as the rest. A pure function of its arguments: no configuration
    field, no environment variable. ``num_bins``, ``mode`` and
    ``epilogue`` are what a body's VMEM depends on besides its width;
    every combination the kernels compile for takes the same width today
    (the int32 planes of ``q8`` are as large as the f32 ones, ``highest``
    differs in its one-hot chunk, not in a plane, and the epilogue form
    holds the block's planes single-buffered), so they are not read."""
    del num_bins, mode, epilogue
    if f <= ONE_BLOCK_FEATURES:
        return f
    blocks = max(2, -(-f // MAX_FEATURE_BLOCK))
    return _round_up(-(-f // blocks), 8)


def feature_blocks(f: int, fb: int) -> int:
    """Feature blocks a kernel launch walks."""
    return -(-f // fb) if f else 1


def _accumulate_block(binsT_ref, leaf_ref, stats_ref, chan_ref, out_ref,
                      *, f, b, c, s, mode):
    """Accumulate one [C]-row grid block into ``out_ref``."""
    kw = dict(f=f, b=b, s=s, mode=mode)
    if c <= _CHUNK or c % _CHUNK:
        _accumulate(binsT_ref[...], leaf_ref[0, :], stats_ref[...],
                    chan_ref[0, :], out_ref, c=c, **kw)
        return

    def chunk(i, carry):
        rows = pl.ds(pl.multiple_of(i * _CHUNK, _CHUNK), _CHUNK)
        _accumulate(binsT_ref[:, rows], leaf_ref[0, rows],
                    stats_ref[rows, :], chan_ref[0, :], out_ref,
                    c=_CHUNK, **kw)
        return carry

    jax.lax.fori_loop(0, c // _CHUNK, chunk, 0)


def _fused_kernel(binsT_ref, leaf_ref, stats_ref, chan_ref, out_ref,
                  *, f, b, c, s, mode, row_axis):
    """Fused kernel: leaf channels built in kernel, rows streamed
    block-by-block straight from the bin matrix (fusion 1). ``f`` is the
    feature block's width; ``out_ref`` that block's planes, zeroed at its
    first row step."""
    @pl.when(pl.program_id(row_axis) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    _accumulate_block(binsT_ref, leaf_ref, stats_ref, chan_ref, out_ref,
                      f=f, b=b, c=c, s=s, mode=mode)


def _grid(nfb: int, nblk: int):
    """(grid, feature-block index of a grid point, its row-block index).
    One feature block is a grid over the row blocks alone, the kernel as
    it was before there were feature blocks; more put the feature blocks
    on an outer axis, so the rows are innermost and a block's accumulator
    stays in VMEM from its first row step to its last."""
    if nfb == 1:
        return (nblk,), (lambda i: 0), (lambda i: i)
    return (nfb, nblk), (lambda j, i: j), (lambda j, i: i)


def _call_kwargs(interpret: bool, axes: int = 1) -> dict:
    if interpret:
        return {"interpret": True}
    return {"compiler_params": pltpu.CompilerParams(
        # a feature block is independent of the others; the row steps of
        # one accumulate into the block's planes
        dimension_semantics=("parallel",) * (axes - 1) + ("arbitrary",),
        # the default 16M scoped-vmem cap rejects the q8 mode at full
        # Higgs scale (int8 accumulation needs a 28.31M stack allocation
        # at block=2048, F=28, B=255); the kernel's working set is still
        # far below the 128M physical VMEM, so raise the cap rather than
        # shrink the block
        vmem_limit_bytes=100 * 1024 * 1024)}


def _row_specs(fb, c, s, feat, row):
    """BlockSpecs of the per-row operands (bins, leaf ids, stats) and the
    lane table, shared by both kernel forms. Only the bins are blocked
    over features: the leaf ids and the statistics of a row block are
    read again, and the rhs built again, for every feature block."""
    return [
        pl.BlockSpec((fb, c), lambda *g: (feat(*g), row(*g))),
        pl.BlockSpec((1, c), lambda *g: (0, row(*g))),
        pl.BlockSpec((c, s), lambda *g: (row(*g), 0)),
        pl.BlockSpec((1, _PAD), lambda *g: (0, 0)),
    ]


@functools.partial(jax.jit,
                   static_argnames=("num_bins", "block", "mode", "interpret",
                                    "fblock"))
def _fused_call(binsT, leaf2d, stats, chan, *, num_bins, block, mode,
                interpret=False, fblock=None):
    """Launch: N must be padded to a ``block`` multiple (pad leaf ids with
    -2 so padding matches no lane) and F to a ``fblock`` multiple."""
    f, n = binsT.shape
    s = stats.shape[1]
    fb = fblock or f
    rows = fb * _bin_rows(num_bins)
    grid, feat, row = _grid(f // fb, n // block)
    kernel = functools.partial(_fused_kernel, f=fb, b=num_bins, c=block, s=s,
                               mode=mode, row_axis=len(grid) - 1)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=_row_specs(fb, block, s, feat, row),
        out_specs=pl.BlockSpec((rows, _PAD), lambda *g: (feat(*g), 0)),
        out_shape=jax.ShapeDtypeStruct(
            (f * _bin_rows(num_bins), _PAD),
            jnp.int32 if mode == "q8" else jnp.float32),
        name=f"{KERNEL_NAME}_{mode}",
        **_call_kwargs(interpret, len(grid)),
    )(binsT, leaf2d, stats, chan)


def _row_operands(binsT, leaf_ids, stats, block: int, mode: str,
                  fblock: int = 0):
    """The kernels' per-row operands, padded to a whole number of row
    blocks and of feature blocks: (binsT, leaf2d, stats, block used).
    Padding rows carry leaf id -2, which matches no lane; a padding
    feature is all bin 0 and its planes are cut off again
    (_planes_to_tile)."""
    f, n = binsT.shape
    leaf2d = leaf_ids[None, :].astype(jnp.int32)
    if mode != "q8":
        stats = stats.astype(jnp.float32)
    c = min(block, max(512, _round_up(n, 512)))
    pad = _round_up(n, c) - n
    fpad = _round_up(f, fblock or f or 1) - f
    if pad or fpad:
        # loop-invariant: XLA hoists these pads out of the grower's
        # while_loop, so the padded copies are built once per program,
        # not once per pass
        binsT = jnp.pad(binsT, ((0, fpad), (0, pad)))
    if pad:
        stats = jnp.pad(stats, ((0, pad), (0, 0)))
        leaf2d = jnp.pad(leaf2d, ((0, 0), (0, pad)), constant_values=-2)
    return binsT, leaf2d, stats, c


def _planes_to_tile(plane, f, b, p, s):
    """[F' * _bin_rows(B), _PAD] kernel plane -> [P, F, B, S] tile, F' the
    width padded to whole feature blocks."""
    return (plane.reshape(-1, _bin_rows(b), _PAD)[:f, :b, :p * s]
            .reshape(f, b, p, s).transpose(2, 0, 1, 3))


def histogram_tiles_pallas_mode(binsT, stats, leaf_ids, sel, num_bins,
                                block=DEFAULT_BLOCK, mode="hilo",
                                interpret=False, fblock=None):
    """[P, F, B, S] histogram tile via the fused kernel.

    ``mode``: "hilo" (2-pass bf16, the fast f32 default), "highest"
    (6-pass, precise), or "q8" (int8 stats -> exact int32 histograms for
    the quantized-gradient training mode; ~2x hilo's MXU rate).
    Takes the FEATURE-MAJOR bin matrix [F, N].

    ``interpret=True`` runs the kernel through the Pallas interpreter
    (CPU-testable; Config.hist_pallas_interpret). ``fblock`` is internal:
    the feature block's width, ``feature_block``'s answer unless a test
    forces another.
    """
    f = binsT.shape[0]
    p = sel.shape[0]
    s = stats.shape[1]
    assert p * s <= _PAD, (p, s)
    fb = fblock or feature_block(f, num_bins, mode)
    binsT, leaf2d, stats, c = _row_operands(binsT, leaf_ids, stats, block,
                                            mode, fb)
    out = _fused_call(binsT, leaf2d, stats, chan_leaf_table(sel, s),
                      num_bins=num_bins, block=c, mode=mode,
                      interpret=interpret, fblock=fb)
    return _planes_to_tile(out, f, num_bins, p, s)


# ------------------------------------------------- split-finding epilogue
#
# The fused split epilogue (ISSUE 12): after the last grid step has
# accumulated the tile's histogram planes in VMEM, the kernel walks the
# features and, on each feature's [bins, lanes] slab, runs the numerical
# split-gain scan (ops/split.py scan_candidates, the same function the XLA
# twin calls) over every slot at once, reducing the feature to one best
# candidate per slot — ONCE PER LANE GROUP. The 128 lanes the MXU
# accumulates carry only leaves that are computed from rows (slot q's
# computed leaf: group 0, the accumulator itself); each slot's DERIVED
# sibling lives in a second group that only the epilogue sees, as
# parent[q] - acc[q] on the SAME lanes (group 1: no roll, no lane of the
# contraction spent on a leaf that reads no rows). Group 1 exists a
# feature slab at a time, for its scan; only the candidate tables of both
# groups and the computed plane leave VMEM, the derived planes that stay
# resident as the next level's parents are the same float32 subtraction
# in XLA after the call, and the grower's split phase never touches
# [L, F, B, S] planes again.
#
# Everything in the slab pass is elementwise, a lane roll, a loop over
# rows or a max along the bin axis: what Mosaic lowers (it has no cumsum,
# argmax over a gathered axis, or 4-D relayout). The grad/hess/count channels of
# a slot sit in adjacent lanes; rolling the slab by one and two lanes
# lines all three up on the slot's first lane, where the scan's results
# are read (the other lanes compute throw-away values).

# rows of the per-lane epilogue table (see _epilogue_lanes)
_LANE_DERIVE, _LANE_QSCALE = 0, 1
_LANE_SUM_G, _LANE_SUM_H, _LANE_CNT, _LANE_OUT = 2, 3, 4, 5
_LANE_MIN, _LANE_MAX = 6, 7
# sublanes of one feature's candidate block (>= CAND_CHANNELS, tile-aligned)
_CAND_ROWS = 16


def _epilogue_lanes(sel_derived, leaf_aux, s: int, q_scale=None):
    """[2, 8, _PAD] f32 per-lane epilogue tables, one per lane group
    (0 = the computed leaves, 1 = their derived siblings): lane q belongs
    to slot p_of_q and carries whether the slot has a derived sibling
    (group 1's row; group 0's is zero), the dequant scale (per stat
    channel) and the group's leaf aggregates for that slot
    (pack_leaf_aux columns 0..5 of ``leaf_aux[group]``)."""
    p = sel_derived.shape[0]
    p_of_q, s_of_q, valid = _chan_layout(p, s)
    pq = jnp.asarray(p_of_q)
    dl = (jnp.asarray(valid) & (sel_derived[pq] >= 0)).astype(jnp.float32)
    ql = (jnp.ones((_PAD,), jnp.float32) if q_scale is None
          else q_scale[jnp.asarray(s_of_q)].astype(jnp.float32))
    la = leaf_aux.astype(jnp.float32)[:, pq]                 # [2, _PAD, 8]
    return jnp.stack([jnp.stack([jnp.zeros_like(dl), dl]),
                      jnp.broadcast_to(ql, (2, _PAD))]
                     + [la[:, :, k] for k in range(6)], axis=1)


def _epilogue_params(pv):
    """Rebuild the 7 numerical-scan SplitParams fields from the packed
    scalars (unused fields zeroed). ``pv`` is anything indexable by 0..6:
    the [7] vector in XLA, the SMEM ref in kernel."""
    from .split import SplitParams
    z = jnp.float32(0.0)
    return SplitParams(
        lambda_l1=pv[0], lambda_l2=pv[1], max_delta_step=pv[2],
        path_smooth=pv[3], min_data_in_leaf=pv[4],
        min_sum_hessian_in_leaf=pv[5], min_gain_to_split=pv[6],
        cat_l2=z, cat_smooth=z, max_cat_threshold=jnp.int32(0),
        min_data_per_group=z, max_cat_to_onehot=jnp.int32(0),
        monotone_penalty=z, cegb_tradeoff=z, cegb_penalty_split=z)


def _epilogue_feature(j, acc_ref, parent_ref, lanes_ref, fm_ref, pv_ref,
                      plane_ref, cand_ref, cs_ref, *, b, mode, with_monotone):
    """Epilogue for feature ``j``: finish its slab of the computed plane
    (dequant) and reduce it, and then the derived siblings' slab, to one
    candidate per slot."""
    from .split import _round_fence, excluded_bins, scan_candidates
    bp = _bin_rows(b)
    params = _epilogue_params(pv_ref)
    rows = pl.ds(pl.multiple_of(j * bp, 8), bp)

    def lane(g, k):
        return lanes_ref[g, k:k + 1, :]

    acc = acc_ref[rows, :]
    if mode == "q8":
        # the dequant product must round to concrete bits BEFORE the
        # sibling subtraction below — a multiply contracted into the
        # subtract would differ per compilation context (e.g. across
        # compaction-rung branches), breaking the ladder-invariance the
        # exact integer accumulation guarantees (ops/split.py _round_fence)
        plane = _round_fence(acc.astype(jnp.float32) * lane(0, _LANE_QSCALE),
                             params)
    else:
        plane = acc
    plane_ref[rows, :] = plane

    pos = jax.lax.broadcasted_iota(jnp.int32, (bp, _PAD), 0)
    nb, mt, db, mono = (fm_ref[j, k] for k in range(4))
    excl = excluded_bins(pos, nb, mt, db)
    row = jax.lax.broadcasted_iota(jnp.int32, (_CAND_ROWS, _PAD), 0)

    def chan(x, k):
        # stat channel k of every slot, lined up on the slot's first lane
        return x if k == 0 else pltpu.roll(x, _PAD - k, 1)

    def scan_group(g, full):
        # ops/split.py prefix_sum's recurrence, in place over the slab's
        # rows
        cs_ref[...] = jnp.where(excl, 0.0, full)

        def step(t, run):
            run = run + cs_ref[pl.ds(t, 1), :]
            cs_ref[pl.ds(t, 1), :] = run
            return run

        jax.lax.fori_loop(0, b, step, jnp.zeros((1, _PAD), jnp.float32))
        cs = cs_ref[...]
        tot = cs[b - 1:b, :]
        chans = scan_candidates(
            cs, chan(cs, 1), chan(cs, 2), tot, chan(tot, 1), chan(tot, 2),
            pos, 0, b, lane(g, _LANE_SUM_G), lane(g, _LANE_SUM_H),
            lane(g, _LANE_CNT), lane(g, _LANE_OUT), nb, mt, db, mono,
            params, with_monotone=with_monotone,
            leaf_min=lane(g, _LANE_MIN), leaf_max=lane(g, _LANE_MAX))
        blk = jnp.zeros((_CAND_ROWS, _PAD), jnp.float32)
        for k, v in enumerate(chans):
            blk = jnp.where(row == k, v, blk)
        cand_ref[j, g] = blk

    scan_group(0, plane)
    # group 1: slot q's derived sibling is parent[q] - computed[q], on the
    # slot's own lanes; it exists for this scan only (the caller rebuilds
    # the planes it keeps from the same two operands)
    scan_group(1, jnp.where(lane(1, _LANE_DERIVE) != 0,
                            parent_ref[rows, :] - plane, 0.0))


def _fused_epi_kernel(binsT_ref, leaf_ref, stats_ref, chan_ref, parent_ref,
                      lanes_ref, fm_ref, pv_ref, plane_ref, cand_ref,
                      acc_ref, cs_ref, *, f, b, c, s, mode, nblk, row_axis,
                      with_monotone):
    """Fused kernel WITH the split epilogue: accumulation runs in a VMEM
    scratch; a feature block's last row step scans both lane groups
    (computed leaves, derived siblings) of its ``f`` features and writes
    their computed plane and candidate tables once."""
    i = pl.program_id(row_axis)

    @pl.when(i == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    _accumulate_block(binsT_ref, leaf_ref, stats_ref, chan_ref, acc_ref,
                      f=f, b=b, c=c, s=s, mode=mode)

    @pl.when(i == nblk - 1)
    def _epi():
        def body(j, carry):
            _epilogue_feature(j, acc_ref, parent_ref, lanes_ref, fm_ref,
                              pv_ref, plane_ref, cand_ref, cs_ref, b=b,
                              mode=mode, with_monotone=with_monotone)
            return carry

        jax.lax.fori_loop(0, f, body, 0)


@functools.partial(jax.jit,
                   static_argnames=("num_bins", "block", "mode", "interpret",
                                    "with_monotone", "fblock"))
def _fused_epi_call(binsT, leaf2d, stats, chan, parent, lanes, fm, pv, *,
                    num_bins, block, mode, interpret=False,
                    with_monotone=False, fblock=None):
    """Launch of the epilogue form: as _fused_call, and the parent planes,
    the scan metadata ``fm`` and both outputs blocked over features like
    the bins."""
    f, n = binsT.shape
    s = stats.shape[1]
    fb = fblock or f
    rows = fb * _bin_rows(num_bins)
    nblk = n // block
    grid, feat, row = _grid(f // fb, nblk)
    kernel = functools.partial(_fused_epi_kernel, f=fb, b=num_bins, c=block,
                               s=s, mode=mode, nblk=nblk,
                               row_axis=len(grid) - 1,
                               with_monotone=with_monotone)
    # a block's parent planes are read, and its computed plane written,
    # once in the block's whole sweep over the rows: a second buffer would
    # hide a copy of microseconds behind a sweep of milliseconds, at a
    # plane of VMEM each
    once = {} if len(grid) == 1 else {"pipeline_mode": pl.Buffered(1)}
    planes = pl.BlockSpec((rows, _PAD), lambda *g: (feat(*g), 0), **once)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    # one block: the whole table, as before; more: the block's own rows
    # (a whole [F, 4] table of a wide matrix does not fit SMEM, whose rows
    # pad to 128 words)
    fm_spec = smem if len(grid) == 1 else pl.BlockSpec(
        (fb, fm.shape[1]), lambda *g: (feat(*g), 0),
        memory_space=pltpu.SMEM)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=_row_specs(fb, block, s, feat, row) + [
            planes,                                          # parent
            pl.BlockSpec((2, 8, _PAD), lambda *g: (0, 0, 0)),  # lane tables
            fm_spec, smem,                                   # fm, pv
        ],
        out_specs=(planes,
                   pl.BlockSpec((fb, 2, _CAND_ROWS, _PAD),
                                lambda *g: (feat(*g), 0, 0, 0))),
        out_shape=(jax.ShapeDtypeStruct((f * _bin_rows(num_bins), _PAD),
                                        jnp.float32),
                   jax.ShapeDtypeStruct((f, 2, _CAND_ROWS, _PAD),
                                        jnp.float32)),
        scratch_shapes=[
            pltpu.VMEM((rows, _PAD),
                       jnp.int32 if mode == "q8" else jnp.float32),
            pltpu.VMEM((_bin_rows(num_bins), _PAD), jnp.float32)],
        name=f"{EPILOGUE_KERNEL_NAME}_{mode}",
        **_call_kwargs(interpret, len(grid)),
    )(binsT, leaf2d, stats, chan, parent, lanes, fm, pv)


def pack_leaf_aux(sum_g, sum_h, cnt, output, leaf_min=None, leaf_max=None):
    """[P, 8] f32 per-slot leaf aggregates for the epilogue kernel
    (columns: sum_g, sum_h, cnt, output, min, max, 0, 0)."""
    p = sum_g.shape[0]
    big = np.float32(np.finfo(np.float32).max)
    lmin = (jnp.full((p,), -big) if leaf_min is None
            else leaf_min.astype(jnp.float32))
    lmax = (jnp.full((p,), big) if leaf_max is None
            else leaf_max.astype(jnp.float32))
    cols = [sum_g, sum_h, cnt, output, lmin, lmax,
            jnp.zeros((p,)), jnp.zeros((p,))]
    return jnp.stack([a.astype(jnp.float32) for a in cols], axis=1)


def pack_feature_meta(num_bins_f, missing_type_f, default_bin_f, monotone_f):
    """[F, 8] f32 per-feature scan metadata for the epilogue kernel
    (columns: num_bins, missing_type, default_bin, monotone, 0...)."""
    f = num_bins_f.shape[0]
    cols = [num_bins_f, missing_type_f, default_bin_f, monotone_f]
    cols = [a.astype(jnp.float32) for a in cols] + [jnp.zeros((f,))] * 4
    return jnp.stack(cols, axis=1)


def pack_scan_params(p) -> jax.Array:
    """[7] f32 packed numerical-scan SplitParams for the epilogue kernel
    (inverse of _epilogue_params)."""
    return jnp.stack([
        p.lambda_l1, p.lambda_l2, p.max_delta_step, p.path_smooth,
        p.min_data_in_leaf, p.min_sum_hessian_in_leaf,
        p.min_gain_to_split]).astype(jnp.float32)


def histogram_tiles_pallas_epilogue(binsT, stats, leaf_ids, sel, sel_derived,
                                    parent_planes, leaf_aux, fmeta, pvec,
                                    num_bins, block=DEFAULT_BLOCK, mode="hilo",
                                    interpret=False, with_monotone=False,
                                    q_scale=None, fblock=None):
    """Fused histogram pass + in-kernel split epilogue.

    Args beyond histogram_tiles_pallas_mode:
      sel: [P] leaf per slot, every one COMPUTED from rows (-1 = inactive
        slot): all the kernel's output lanes carry streamed leaves.
      sel_derived: [P] the leaf slot q DERIVES as parent - computed (its
        sibling), or -1 where the computed leaf has none: the root, a leaf
        whose sibling is not pending, a pair whose parent plane is gone.
        An entry >= 0 implies ``sel`` is live there. Derived leaves read
        no rows and take no lane of the contraction.
      parent_planes: [P, F, B, S] f32 parent histogram of slot q's pair
        (zeros where ``sel_derived`` is -1; XLA-gathered from the grower's
        resident state, the one plane-sized read the subtraction needs).
      leaf_aux: [2, P, 8] from pack_leaf_aux: the computed leaves'
        aggregates, then the derived leaves'.
      fmeta: [F, 8] from pack_feature_meta.
      pvec: [7] from pack_scan_params.
      q_scale: [S] dequant scale for mode="q8" (the grower's per-tree
        scales; the kernel dequantizes before deriving, so subtraction
        runs in f32 exactly like the classic XLA flow).

    Returns (tile [2P, F, B, S] f32, cand [2P, F, CAND_CHANNELS]): the P
    computed leaves, then the P derived ones (zeros where there is none).
    The kernel emits the computed plane alone; the derived planes, which
    stay resident for the next level's subtraction, are the same float32
    ``parent - computed`` taken here in XLA from the kernel's output.
    """
    from .split import CAND_CHANNELS
    f = binsT.shape[0]
    p = sel.shape[0]
    s = stats.shape[1]
    assert s == 3, "the split epilogue expects (grad, hess, count) stats"
    assert p * s <= _PAD, (p, s)
    bp = _bin_rows(num_bins)
    fb = fblock or feature_block(f, num_bins, mode, epilogue=True)
    fpad = _round_up(f, fb) - f
    lanes = _epilogue_lanes(sel_derived, leaf_aux, s,
                            q_scale if mode == "q8" else None)
    parent_planes = parent_planes.astype(jnp.float32)
    parent = jnp.pad(
        parent_planes.transpose(1, 2, 0, 3).reshape(f, num_bins, p * s),
        ((0, fpad), (0, bp - num_bins), (0, _PAD - p * s))
    ).reshape((f + fpad) * bp, _PAD)
    binsT, leaf2d, stats, c = _row_operands(binsT, leaf_ids, stats, block,
                                            mode, fb)
    chan = chan_leaf_table(sel, s)
    fm = fmeta[:, :4].astype(jnp.int32)
    if fpad:
        # a padding feature has no bin to scan; its candidates are cut off
        fm = jnp.pad(fm, ((0, fpad), (0, 0)))
    plane, craw = _fused_epi_call(
        binsT, leaf2d, stats, chan, parent, lanes, fm,
        jnp.pad(pvec.astype(jnp.float32), (0, 1)),
        num_bins=num_bins, block=c, mode=mode, interpret=interpret,
        with_monotone=with_monotone, fblock=fb)
    # each slot's candidate sits on the slot's first lane of its group
    cand = (craw[:f, :, :CAND_CHANNELS, 0:p * s:s].transpose(1, 3, 0, 2)
            .reshape(2 * p, f, CAND_CHANNELS))
    tile = _planes_to_tile(plane, f, num_bins, p, s)
    return jnp.concatenate(
        [tile, derived_planes(tile, sel_derived, parent_planes)]), cand


def derived_planes(tile, sel_derived, parent_planes):
    """[P, F, B, S] planes of the derived siblings: parent - computed
    where slot q derives one, zeros elsewhere. The kernel epilogue's
    group 1, the XLA twin and the planes the grower keeps are all this
    one float32 subtraction of the same two operands."""
    return jnp.where((sel_derived >= 0)[:, None, None, None],
                     parent_planes - tile, 0.0)


# ---------------------------------------------------------------- roofline

# MXU input-rate multiplier per mode: passes over the same one-hot x rhs
# contraction (hilo = 2 bf16 passes, highest = 6, q8 = 1 int8 pass)
MXU_PASSES = {"hilo": 2, "highest": 6, "q8": 1}


def traffic_model(n, f, b, p, s, mode="hilo", gathered_rows=None):
    """Modeled HBM bytes per histogram tile pass: the fused kernel vs the
    XLA one-hot formulation of the same contraction (which must
    materialize its one-hot and leaf-channel RHS through HBM — each
    counted write+read) vs the pre-fusion kernel (XLA-side [N, 128] RHS).
    Used by the acceptance/traffic tests and scripts/kernel_bench.py; all
    quantities are static byte counts.

    ``gathered_rows``: rows the compaction ladder selected; None = full
    pass over n rows. Every formulation pays the same XLA row gather
    then (index buffer + source rows read, compacted copy written).
    """
    stat_b = 1 if mode == "q8" else 4
    out_b = 4
    rhs_b = 1 if mode == "q8" else (2 * 2 if mode == "hilo" else 4)
    oh_b = 1 if mode == "q8" else (2 if mode == "hilo" else 4)
    m = n if gathered_rows is None else gathered_rows
    out_bytes = f * b * _PAD * out_b
    common = m * f + m * s * stat_b + m * 4          # bins + stats + leaf
    gather = 0 if gathered_rows is None else m * 4 + 2 * common
    fused = common + out_bytes + gather
    # pre-fusion kernel: [N(=m), 128] RHS written by XLA then re-read by
    # the kernel
    prefusion = fused + 2 * m * _PAD * rhs_b
    # XLA one-hot contraction: the [M, F*B] one-hot and the RHS both
    # round-trip HBM (XLA cannot keep either resident across the scan)
    xla_onehot = fused + 2 * m * f * b * oh_b + 2 * m * _PAD * rhs_b
    # split-search consumer bytes per LEAF (ISSUE 12): the classic split
    # phase streams each leaf's [F, B, S=3] f32 histogram plane through
    # the gain scan's temporaries; the fused epilogue returns only the
    # [F, CAND_CHANNELS] candidate row — a >= B/4x reduction in bytes
    # the search reads back from HBM (3*B*4 / (12*4) = exactly B/4 at
    # the 12-channel layout; kernel_bench asserts the floor from the
    # REAL returned buffers, not from this model)
    from .split import CAND_CHANNELS
    search_in_planes = f * b * s * 4
    search_in_cand = f * CAND_CHANNELS * 4
    return {"fused": fused, "prefusion": prefusion,
            "xla_onehot": xla_onehot, "output": out_bytes,
            "search_in_planes": search_in_planes,
            "search_in_cand": search_in_cand}

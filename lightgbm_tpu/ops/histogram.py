"""Per-leaf gradient-statistics histograms on device.

The TPU analog of the reference's histogram construction hot loop
(reference: src/io/dense_bin.hpp:98-141 ``ConstructHistogramInner`` on CPU and
src/treelearner/kernels/histogram_16_64_256.cu on CUDA). The data lives as a
dense binned matrix ``bins[N, F]`` and histograms are built for a TILE of
pending leaves in a single data pass keyed by ``(tile slot, feature, bin)``.

Backends (selected by ``method``):

- ``"onehot"`` (the XLA twin of the Pallas kernels, their fallback): scan
  over fixed-size row blocks; each block builds a transient bin one-hot
  ``[F*B, C]`` (bins-major, as the kernels do) and a leaf-slot one-hot x
  stats ``[C, P*S]`` and contracts them on the MXU. No scatter at all —
  measured on v5e, XLA's scatter-add runs at ~0.06 G updates/s (sequential
  lowering) while this pass is memory/pipeline-bound at ~4 G elem/s nearly
  independent of the tile width P (the one-hot materialization dominates),
  which is why a tile of ~42 leaves costs the same as one. This is the TPU
  re-design of the CUDA sub-histogram kernels
  (histogram_16_64_256.cu:16-120): their shared-memory atomics become a
  dense one-hot contraction.
- ``"scatter"``: one flat scatter-add — the right backend on CPU hosts
  (tests, small data), pathological on TPU.
- ``"binloop"``: loop over bin values with masked einsum reductions; kept for
  small problems and cross-checks.

Accumulation is float32 (the reference CPU path uses float64 ``hist_t``
(bin.h:32); its GPU path defaults to float32 ``gpu_use_dp=false`` with
documented AUC parity (docs/GPU-Performance.rst:133-140) — we follow the GPU
precision model). Counts are accumulated exactly as a third channel rather
than re-derived from the hessian like the reference's
``RoundInt(hess * cnt_factor)`` (feature_histogram.hpp:869).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def histogram_scatter(bins: jax.Array, stats: jax.Array, leaf_ids: jax.Array,
                      num_leaves: int, num_bins: int) -> jax.Array:
    """Flat scatter-add histogram.

    Args:
      bins: [N, F] integer bin matrix.
      stats: [N, S] per-row statistics (grad, hess, count-weight); rows that
        must not contribute (inactive leaves, bagged-out) carry zeros.
      leaf_ids: [N] leaf slot of each row.
      num_leaves: number of leaf slots L (static).
      num_bins: bins per feature B (static).

    Returns:
      [L, F, B, S] float32 histogram.
    """
    n, f = bins.shape
    s = stats.shape[1]
    flat_idx = (leaf_ids[:, None].astype(jnp.int32) * f
                + jnp.arange(f, dtype=jnp.int32)[None, :]) * num_bins + bins.astype(jnp.int32)
    contrib = jnp.broadcast_to(stats.astype(jnp.float32)[:, None, :], (n, f, s))
    hist = jnp.zeros((num_leaves * f * num_bins, s), dtype=jnp.float32)
    hist = hist.at[flat_idx.reshape(-1)].add(contrib.reshape(-1, s))
    return hist.reshape(num_leaves, f, num_bins, s)


def histogram_binloop(bins: jax.Array, stats: jax.Array, leaf_onehot: jax.Array,
                      num_bins: int) -> jax.Array:
    """Histogram via a fori_loop over bin values (no scatter).

    ``leaf_onehot``: [N, L] float32 0/1 row-to-leaf assignment (already masked
    for inactive rows). For each bin value the row mask is a dense compare and
    the (leaf x stat) reduction is a matmul — the design swaps the CUDA
    kernel's shared-memory atomics (histogram_16_64_256.cu:16-120) for
    compare+matmul, which is how a TPU VPU/MXU wants this computation.

    Returns [L, F, B, S].
    """
    n, f = bins.shape
    l = leaf_onehot.shape[1]
    s = stats.shape[1]
    bins = bins.astype(jnp.int32)

    acc_dtype = jnp.result_type(stats.dtype, leaf_onehot.dtype, jnp.float32)

    def body(b, acc):
        mask = (bins == b).astype(acc_dtype)             # [N, F]
        out = jnp.einsum("nl,nf,ns->lfs", leaf_onehot, mask, stats,
                         preferred_element_type=acc_dtype)
        return acc.at[:, :, b, :].set(out)

    acc = jnp.zeros((l, f, num_bins, s), dtype=acc_dtype)
    return jax.lax.fori_loop(0, num_bins, body, acc)


@functools.partial(jax.jit, static_argnames=("num_leaves", "num_bins", "method"))
def build_histograms(bins: jax.Array, stats: jax.Array, leaf_ids: jax.Array,
                     num_leaves: int, num_bins: int,
                     method: str = "scatter") -> jax.Array:
    """Build [L, F, B, S] histograms for all leaf slots in one data pass."""
    if method == "scatter":
        return histogram_scatter(bins, stats, leaf_ids, num_leaves, num_bins)
    elif method == "binloop":
        onehot = jax.nn.one_hot(leaf_ids, num_leaves, dtype=jnp.float32)
        return histogram_binloop(bins, stats, onehot, num_bins)
    raise ValueError(f"unknown histogram method: {method}")


def oom_fallback_method(method: str) -> str:
    """Rung 2 of the OOM degradation ladder (models/gbdt.py
    _maybe_degrade_oom): the minimum-footprint formulation of the same
    histogram contraction. The Pallas kernels pin VMEM tiles and the
    onehot formulations materialize a transient [F*B, C] one-hot per row
    block; ``scatter`` allocates only the [L, F, B, S] output and updates
    it in place — slow on TPU (sequential lowering) but the smallest
    possible working set, which is the point of a degraded-but-alive run.
    Quantized methods keep their exact-integer accumulation via
    ``onehot_q8`` (scatter has no integer form — resolve_method's rule)."""
    if method.endswith("_q8"):
        return "onehot_q8"
    return "scatter"


def subtract_histogram(parent: jax.Array, child: jax.Array) -> jax.Array:
    """Histogram subtraction trick: sibling = parent - child
    (reference: serial_tree_learner.cpp:311-320, feature_histogram.hpp:79)."""
    return parent - child


@jax.named_scope("rung_gather")
def compact_indices(keep: jax.Array, size: int) -> jax.Array:
    """[size] int32 prefix-sum compaction of the ``keep`` rows' indices
    (original row order — jnp.nonzero is stable); padding slots carry N.
    This is the compaction ladder's row-index buffer, which
    histogram_tiles expands into compacted copies for every backend."""
    n = keep.shape[0]
    return jnp.nonzero(keep, size=size, fill_value=n)[0].astype(jnp.int32)


@jax.named_scope("rung_gather")
def gather_rows(bins: jax.Array | None, binsT: jax.Array | None,
                stats: jax.Array, leaf_ids: jax.Array, idx: jax.Array):
    """Expand a compaction row-index buffer (compact_indices output) into
    statically-shaped compacted copies of ``idx.shape[0]`` rows — the
    shape-static analog of the reference's permuted per-leaf row partition
    (data_partition.hpp:21-60): a tile pass over the compacted buffer
    costs O(size) instead of O(N).

    The kept rows land in ORIGINAL row order (jnp.nonzero is a stable
    prefix-sum compaction), so a scatter-add histogram over the buffer
    accumulates each cell's contributions in exactly the order of the
    full-N pass — bit-identical sums there; the matmul backends regroup
    partial sums (see the onehot scan) and match to accumulation-order
    tolerance like every other pass-shape change.

    Padded slots (idx >= N) carry zero stats and leaf id -2, which matches
    no tile ``sel`` entry (active slots are >= 0, inactive -1), so every
    backend drops them. Rows are gathered from the ROW-major matrix where
    there is one — one contiguous F-byte read per row, against F single
    elements from the feature-major copy — and the feature-major result
    is its transpose.

    Args:
      bins: [N, F] row-major bin matrix or None (sparse-only datasets).
      binsT: [F, N] feature-major copy or None.
      stats: [N, S] per-row statistics (any accumulation dtype).
      leaf_ids: [N] int32 leaf slot per row.
      idx: [size] int32 row indices.

    Returns:
      (bins_c, binsT_c, stats_c, leaf_ids_c) with ``size`` rows each
      (None stays None).
    """
    n = leaf_ids.shape[0]
    ok = idx < n
    idxc = jnp.minimum(idx, n - 1)
    stats_c = jnp.where(ok[:, None], jnp.take(stats, idxc, axis=0),
                        jnp.zeros((), stats.dtype))
    leaf_ids_c = jnp.where(ok, jnp.take(leaf_ids, idxc), jnp.int32(-2))
    bins_c = None if bins is None else jnp.take(bins, idxc, axis=0)
    if binsT is None:
        binsT_c = None
    elif bins_c is not None:
        binsT_c = bins_c.T
    else:
        binsT_c = jnp.take(binsT, idxc, axis=1)
    return bins_c, binsT_c, stats_c, leaf_ids_c


def compact_rows(bins: jax.Array | None, binsT: jax.Array | None,
                 stats: jax.Array, leaf_ids: jax.Array, keep: jax.Array,
                 size: int):
    """gather_rows over the ``keep`` rows ([N] bool): prefix-sum
    compaction into ``size``-row buffers. The caller guarantees
    ``sum(keep) <= size`` (the grower's ladder dispatch conditions on the
    pending row count)."""
    return gather_rows(bins, binsT, stats, leaf_ids,
                       compact_indices(keep, size))


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


# What one compaction rung costs, per row, by device kind: the ONE table
# behind prune_compaction_ladder. A kind (or, within a kind, a histogram
# method) without an entry prunes nothing. Every constant is fitted to
# readings on that chip: count, index and gather to one tile pass of
# scripts/calibrate_compaction.py at block 2048 (PR 28), kernel_ns again
# at pallas_hist.DEFAULT_BLOCK to the bins-major body of PR 36 (the
# calibration and scripts/kernel_bench.py; PERF.md, Findings, PR 36, holds
# the readings):
#   count_ns   the slot_map[leaf_ids] lookup and its sum (models/grower.py
#              tile_build), a row HELD: 8.3-8.8 at 2.1M and 10.5M rows
#   index_ns   compact_indices (jnp.nonzero: cumsum + scatter + cumsum), a
#              row HELD: 9.1-9.4 (11.4 into a 5.25M-slot rung)
#   gather_ns  gather_rows inside the rung, a row GATHERED: (the
#              statistics, the leaf ids and the clamp; each feature of the
#              u8 bin row and its transpose): 40 at F=28 and F=137, 44 at
#              F=274 — XLA's gather costs per index, hardly per byte (the
#              5.25M-row rung of a 10.5M-row table reads 75: not modeled)
#   kernel_ns  the Pallas kernel, a row, by method: (whatever the width;
#              per feature and 128-lane MXU tile of its bin one-hot —
#              nothing is left that a feature costs whatever its bins).
#              pallas_hilo 255 bins: 11.2 / 24.6 / 53.3 / 99.8-101.9 /
#              195.1 at F=8 / 28 / 68 / 137 / 274, 63 bins 10.6 / 28.7 at
#              F=28 / 137, all within 2% of the fit; pallas_q8 255 bins
#              9.3 / 27.1 at F=28 / 137 (its 63-bin form does not
#              compile); pallas (HIGHEST) 21.9 / 68.5 / 155.3 at F=8 / 28
#              / 68
RUNG_COSTS = {
    "TPU v5 lite": {
        "count_ns": 8.5,
        "index_ns": 9.5,
        "gather_ns": (40.0, 0.03),
        "kernel_ns": {"pallas_hilo": (5.6, 0.345),
                      "pallas": (3.9, 1.13),
                      "pallas_q8": (4.7, 0.082)},
    },
}
# the row a TPU kind without one of its own borrows: that a gather costs
# per index is XLA's lowering for the TPU, not one chip's clock
_RUNG_COSTS_TPU_DEFAULT = "TPU v5 lite"


def _onehot_tiles(num_bins: int) -> float:
    """128-lane MXU tiles one feature's bin one-hot takes in the Pallas
    kernels: up to 64 bins, 128 // bins features share a tile
    (pallas_hist._accumulate's feature packing); beyond that a feature
    takes whole tiles."""
    if num_bins <= 128:
        return 1.0 / max(1, 128 // max(num_bins, 1))
    return float(_round_up(num_bins, 128) // 128)


def rung_costs_source(device_kind: str) -> str | None:
    """The RUNG_COSTS row a device kind is priced with: its own, TPU v5
    lite's for a TPU kind without one, None for anything else."""
    if device_kind in RUNG_COSTS:
        return device_kind
    return _RUNG_COSTS_TPU_DEFAULT if device_kind.startswith("TPU") else None


def rung_costs(device_kind: str, method: str, rows: int, features: int,
               num_bins: int, rung_rows: int) -> dict | None:
    """Modeled seconds of a tile pass through a compaction rung of
    ``rung_rows`` rows and of the full pass over ``rows`` rows it
    replaces: ``{"count", "index", "gather", "kernel", "rung", "full"}``.
    ``count`` is ONE pass's count; ``rung`` is what a pass through the
    rung costs the tree: index + gather + kernel + the counts of
    ``rows / (2 * rung_rows)`` passes, because every pass of a grower
    with a ladder pays the count and only the passes whose tile fits take
    the rung — with sibling subtraction every non-root pass fits N/2, and
    of the 12 passes a Higgs tree took when the rule was fitted 4 fit N/8
    (PERF_LEDGER, PR 27: 5.0 N-equivalents = 1 + 7/2 + 4/8; since PR 30 a
    tree takes 9 and the charge was not fitted again).

    None where RUNG_COSTS has no constants for the device kind and
    method; a TPU kind without a row borrows ``TPU v5 lite``'s. Pure
    arithmetic on its arguments."""
    row = RUNG_COSTS.get(rung_costs_source(device_kind))
    k = None if row is None else row["kernel_ns"].get(method)
    if k is None:
        return None
    # what a kernel body pays a row whatever its columns (the rhs build,
    # the statistics' 128 lanes) it pays once a FEATURE BLOCK
    from .pallas_hist import feature_block, feature_blocks
    blocks = feature_blocks(features, feature_block(
        features, num_bins, _KERNEL_MODE[method]))
    kernel_row = k[0] * blocks + k[1] * features * _onehot_tiles(num_bins)
    g = row["gather_ns"]
    out = {"count": row["count_ns"] * rows,
           "index": row["index_ns"] * rows,
           "gather": (g[0] + g[1] * features) * rung_rows,
           "kernel": kernel_row * rung_rows,
           "full": kernel_row * rows}
    out = {name: ns * 1e-9 for name, ns in out.items()}
    counts_per_taken_pass = max(1.0, rows / (2.0 * rung_rows))
    out["rung"] = (out["count"] * counts_per_taken_pass + out["index"]
                   + out["gather"] + out["kernel"])
    return out


def prune_compaction_ladder(candidates: tuple, device_kind: str, method: str,
                            rows: int, features: int,
                            num_bins: int) -> tuple:
    """The candidate rungs (row-buffer sizes) that PAY at this shape: a
    rung of m rows stays only if

        count(N) * max(1, N / 2m) + index(N) + gather(m, F)
            + kernel(m, F, B, method)  <  kernel(N, F, B, method)

    by RUNG_COSTS' per-row constants for the device (rung_costs). The rule
    only prunes, and only where it has constants: on a backend or for a
    histogram method the table does not cover (the CPU, where ``scatter``
    makes every rung pay; the XLA formulations on a TPU) the candidates
    come back as they are. On a TPU v5 lite at the Higgs shape (10.5M x 28,
    255 bins, ``pallas_hilo``) it keeps neither default rung: count and
    index build cost 18 ns a row HELD and XLA's gathers 40-75 ns a row
    gathered, against a kernel of 25 ns a row; at 137 features and 255
    bins (100 ns a row) both default rungs stay, at 63 bins or under
    ``pallas_q8`` (29 and 27 ns a row) neither (PERF.md, PR 28 and
    PR 36)."""
    kept = []
    for m in candidates:
        cost = rung_costs(device_kind, method, rows, features, num_bins, m)
        if cost is None or cost["rung"] < cost["full"]:
            kept.append(m)
    return tuple(kept)


# histogram_method -> the Pallas kernels' precision mode
_KERNEL_MODE = {"pallas": "highest", "pallas_hilo": "hilo",
                "pallas_q8": "q8"}

# (method, reasons) combinations already warned about — one warning per
# distinct degradation, not one per trace
_pallas_fallback_warned: set = set()


def resolve_method(method: str, deterministic: bool = False,
                   quantized: bool = False, interpret: bool = False) -> str:
    """Map ``histogram_method="auto"`` to the platform's fast backend.
    Where the reference times col-wise against row-wise at start-up
    (dataset.cpp:591-689 TestMultiThreadingMethod), the choice here is a
    rule of the platform and nothing is timed: scatter-add is fast on CPU
    hosts and serialized on a TPU, where the fused Pallas kernel is the
    primary path. What the chip bears out (PERF.md): at the Higgs width a
    ``pallas_hilo`` pass takes 9.1 ms where XLA's ``onehot_hilo`` takes
    47.1 (262,144 rows, PR 21), and ``pallas_q8`` is 13-25% cheaper a row
    than ``pallas_hilo`` (PR 28).

    ``pallas_hilo`` rounds grad/hess inputs to a hi+lo bf16 pair (~2^-17
    relative, vs f32's 2^-24) before the MXU contraction; near-tied split
    gains can therefore differ from a full-f32 run. ``deterministic=True``
    (the reference's reproducibility flag, config.h:166) keeps ``auto`` on
    the HIGHEST-precision kernel so results are stable across
    histogram-method choices at ~1.7x the pass cost.

    ``quantized=True`` (Config.quantized_grad, the end-to-end int8
    quantized-gradient training mode) maps the resolved method onto its
    q8 twin: the Pallas kernel on TPU, the XLA int8 contraction elsewhere
    (scatter/binloop have no integer-accumulation form — they resolve to
    onehot_q8 with a one-time note).

    ``interpret=True`` (Config.hist_pallas_interpret) keeps ``auto`` on the
    Pallas kernels OFF-TPU too, running them through the Pallas
    interpreter — the CPU test path for the production TPU pipeline.

    ``histogram_tiles`` falls back from a pallas method to the equivalent
    XLA onehot contraction when the kernel's preconditions don't hold
    (non-TPU backend without interpret, no feature-major bins, f64
    accumulation, or tile_leaves*stats exceeding the 128-lane group) and
    warns once per precondition."""
    on_kernel = jax.default_backend() == "tpu" or interpret
    if quantized:
        if method in ("auto", "pallas", "pallas_hilo", "pallas_q8"):
            return "pallas_q8" if on_kernel else "onehot_q8"
        if method in ("scatter", "binloop"):
            key = ("quantized_grad", method)
            if key not in _pallas_fallback_warned:
                _pallas_fallback_warned.add(key)
                from ..utils import log
                log.info(f"quantized_grad: histogram_method={method!r} has "
                         "no integer-accumulation form; using onehot_q8")
        return "onehot_q8"
    if method == "auto":
        if not on_kernel:
            return "scatter"
        return "pallas" if deterministic else "pallas_hilo"
    return method


@jax.named_scope("hist_pass")
def histogram_tiles(bins: jax.Array, stats: jax.Array, leaf_ids: jax.Array,
                    sel: jax.Array, num_bins: int, method: str = "onehot",
                    block: int = 0, dtype=jnp.float32,
                    binsT: jax.Array | None = None,
                    gather_idx: jax.Array | None = None,
                    interpret: bool = False) -> jax.Array:
    """Histograms for a TILE of leaves.

    Slot ``p`` of the output accumulates the rows whose ``leaf_ids`` equals
    ``sel[p]``; ``sel`` entries < 0 are inactive slots (zero output). This is
    the unit the grower calls once per tile round — on TPU its cost is nearly
    independent of the tile width, so one call covers up to ~42 pending
    leaves.

    Args:
      bins: [N, F] integer bin matrix.
      stats: [N, S] per-row statistics (grad, hess, count-weight), already
        masked for bagging.
      leaf_ids: [N] leaf slot of each row.
      sel: [P] int32 leaf ids selected into this tile (-1 = inactive slot).
      num_bins: bins per feature B (static).
      gather_idx: optional [M] int32 compacted row-index buffer
        (compact_indices output; entries >= N are padding): the pass
        covers the M indexed rows, gathered here into compacted copies
        that every backend, the Pallas kernels included, then streams
        (see pallas_hist's module docstring for why not in kernel).
      interpret: run Pallas kernels through the interpreter (CPU test
        path, Config.hist_pallas_interpret); ignored by XLA backends.

    Returns:
      [P, F, B, S] float32 histogram.
    """
    n, f = bins.shape if bins is not None else binsT.shape[::-1]
    p = sel.shape[0]
    s = stats.shape[1]

    if gather_idx is not None:
        bins, binsT, stats, leaf_ids = gather_rows(bins, binsT, stats,
                                                   leaf_ids, gather_idx)
        n = gather_idx.shape[0]

    if method in ("pallas", "pallas_hilo", "pallas_q8"):
        # the fused kernel needs: real TPU lowering (or the interpreter),
        # the feature-major bin matrix, f32 accumulation, and the tile x
        # stat channels within one 128-lane group; otherwise run the XLA
        # onehot formulation of the same contraction. ``reasons`` IS the
        # gate: empty means every precondition holds, so the warning can
        # never disagree with it.
        reasons = []
        if jax.default_backend() != "tpu" and not interpret:
            reasons.append(f"backend is {jax.default_backend()!r}, not tpu "
                           "(set hist_pallas_interpret=true to emulate)")
        if binsT is None:
            reasons.append("feature-major bin matrix (binsT) unavailable")
        if not (dtype == jnp.float32 or method == "pallas_q8"):
            reasons.append(f"accumulation dtype {jnp.dtype(dtype).name} "
                           "(kernel is f32-only)")
        if p * s > 128:
            reasons.append(f"tile_leaves*stats = {p}*{s} = {p * s} > 128 "
                           "lanes (lower tile_leaves)")
        if not reasons:
            from . import pallas_hist
            return pallas_hist.histogram_tiles_pallas_mode(
                binsT, stats, leaf_ids, sel, num_bins,
                block=block or pallas_hist.DEFAULT_BLOCK,
                mode=_KERNEL_MODE[method],
                interpret=interpret and jax.default_backend() != "tpu")
        # an explicitly requested kernel silently degrading to the XLA
        # formulation is a large perf cliff — name the violated
        # precondition once so the user can tell why
        key = (method, tuple(reasons))
        if key not in _pallas_fallback_warned:
            _pallas_fallback_warned.add(key)
            from ..utils import log
            log.warning(
                f"histogram_method={method!r} fell back to the XLA onehot "
                f"formulation: {'; '.join(reasons)}")
        method = {"pallas": "onehot", "pallas_hilo": "onehot_hilo",
                  "pallas_q8": "onehot_q8"}[method]

    if method in ("onehot", "onehot_hilo", "onehot_q8"):
        # "onehot_q8": int8 MXU contraction for QUANTIZED stats (the
        # opt-in quantized-gradient mode, see grower.py): stats arrive as
        # int8 channels, the one-hot is exact in int8, products accumulate
        # in int32 — exact integer histograms the caller dequantizes
        q8 = method == "onehot_q8"
        hilo = method == "onehot_hilo" and dtype == jnp.float32
        c = min(block or 16384, _round_up(max(n, 1), 512))
        pad = _round_up(n, c) - n
        if pad:
            bins = jnp.pad(bins, ((0, pad), (0, 0)))
            stats = jnp.pad(stats, ((0, pad), (0, 0)))
            leaf_ids = jnp.pad(leaf_ids, (0, pad), constant_values=-1)
        nblk = (n + pad) // c
        iota_b = jnp.arange(num_bins, dtype=jnp.int32)
        # Off the TPU the float forms pad the channels to whole 128-lane
        # groups, the width the Pallas kernels contract: with the one-hot
        # bins-major too, the twin hands its backend the kernel's
        # [M, K] x [K, N], and the CPU backend (whose summation order
        # follows the shapes) sums it as it sums the interpreted kernel's —
        # bit for bit at a matched row partition. The TPU pads the lanes
        # itself and XLA fuses the unpadded form far better (PERF.md,
        # Findings, PR 36); integer sums need no such care anywhere.
        pad_lanes = not q8 and jax.default_backend() != "tpu"
        w = _round_up(p * s, 128) if pad_lanes else p * s

        def body(acc, xs):
            b, st, lid = xs
            # bins-major one-hot [F*B, C], as pallas_hist._accumulate builds it
            oh_bool = (b.astype(jnp.int32).T[:, None, :]
                       == iota_b[None, :, None]).reshape(f * num_bins, c)
            if q8:
                rhs = jnp.where((lid[:, None] == sel[None, :])[:, :, None],
                                st[:, None, :], jnp.int8(0)).reshape(c, p * s)
                h = jax.lax.dot_general(oh_bool.astype(jnp.int8), rhs,
                                        (((1,), (0,)), ((), ())),
                                        preferred_element_type=jnp.int32)
                return acc + h, None
            lo = (lid[:, None] == sel[None, :]).astype(dtype)  # [C, P]
            rhs = (lo[:, :, None] * st.astype(dtype)[:, None, :]
                   ).reshape(c, p * s)
            rhs = jnp.pad(rhs, ((0, 0), (0, w - p * s)))
            if hilo:
                # hi/lo bf16 decomposition: the one-hot side is exact in
                # bf16 (0/1) and the stat side is split into two bf16 parts
                # whose matmul contributions accumulate in f32 on the MXU —
                # 2 bf16 passes instead of the 6 that Precision.HIGHEST
                # costs on f32 inputs. Inputs round at ~2^-17 relative
                # (hi+lo carries ~16-17 mantissa bits vs f32's 24); sums
                # accumulate in f32 either way. Comparable precision model
                # to the reference GPU's float32 histograms
                # (gpu_use_dp=false, docs/GPU-Performance.rst:133-140),
                # with slightly coarser input rounding; counts are exact
                # (0/1 in bf16).
                from .pallas_hist import split_hilo
                h2 = jax.lax.dot_general(oh_bool.astype(jnp.bfloat16),
                                         split_hilo(rhs),
                                         (((1,), (0,)), ((), ())),
                                         preferred_element_type=jnp.float32)
                h = h2[:, :w] + h2[:, w:]
            else:
                # HIGHEST precision: TPU matmuls otherwise truncate inputs to
                # bf16, corrupting grad/hess sums ~0.5% (the one-hot side is
                # exact either way; counts accumulate exactly in f32
                # regardless)
                h = jax.lax.dot_general(oh_bool.astype(dtype), rhs,
                                        (((1,), (0,)), ((), ())),
                                        precision=jax.lax.Precision.HIGHEST,
                                        preferred_element_type=dtype)
            return acc + h, None

        acc_dtype = jnp.int32 if q8 else dtype
        h, _ = jax.lax.scan(
            body, jnp.zeros((f * num_bins, w), acc_dtype),
            (bins.reshape(nblk, c, f), stats.reshape(nblk, c, s),
             leaf_ids.reshape(nblk, c)))
        return (h[:, :p * s].reshape(f, num_bins, p, s)
                .transpose(2, 0, 1, 3))

    # slot index per row: position of its leaf in sel, or P (dropped)
    eq = leaf_ids[:, None] == sel[None, :]                        # [N, P]
    if method == "scatter":
        slot = jnp.where(jnp.any(eq, axis=1),
                         jnp.argmax(eq, axis=1).astype(jnp.int32),
                         jnp.int32(p))
        flat_idx = (slot[:, None] * f
                    + jnp.arange(f, dtype=jnp.int32)[None, :]) * num_bins \
            + bins.astype(jnp.int32)
        contrib = jnp.broadcast_to(stats.astype(dtype)[:, None, :],
                                   (n, f, s))
        hist = jnp.zeros(((p + 1) * f * num_bins, s), dtype=dtype)
        hist = hist.at[flat_idx.reshape(-1)].add(contrib.reshape(-1, s))
        return hist.reshape(p + 1, f, num_bins, s)[:p]
    elif method == "binloop":
        onehot = eq.astype(dtype)
        return histogram_binloop(bins, stats.astype(dtype), onehot, num_bins)
    raise ValueError(f"unknown histogram method: {method}")


def epilogue_supported(method: str, binsT, p: int, s: int, dtype,
                       interpret: bool = False) -> bool:
    """Whether the IN-KERNEL form of the split epilogue can run (same
    preconditions as the plain pallas kernels). When False,
    histogram_tiles_with_candidates runs the XLA twin of the identical
    epilogue math instead — the fused-search path works on every backend,
    only the kernel fusion degrades."""
    if method not in ("pallas", "pallas_hilo", "pallas_q8"):
        return False
    if jax.default_backend() != "tpu" and not interpret:
        return False
    if binsT is None or p * s > 128 or s != 3:
        return False
    return dtype == jnp.float32 or method == "pallas_q8"


@jax.named_scope("hist_pass")
def histogram_tiles_with_candidates(bins, stats, leaf_ids, sel, sel_derived,
                                    parent_planes, leaf_aux, fmeta, pvec,
                                    num_bins, method: str = "onehot",
                                    block: int = 0, dtype=jnp.float32,
                                    binsT=None, gather_idx=None,
                                    interpret: bool = False,
                                    with_monotone: bool = False,
                                    q_scale=None):
    """Histogram tile pass + fused split-finding epilogue.

    The frontier-batched unit of the ``split_fusion`` grower path: one
    launch histograms the tile's P COMPUTED leaves (``sel``), derives
    slot q's sibling ``sel_derived[q]`` (where it has one) as
    parent[q] - computed[q], and reduces every (leaf, feature) of both
    groups to its best numerical split candidate (ops/split.py
    numerical_candidates). On the Pallas methods the whole epilogue runs
    IN KERNEL (pallas_hist.histogram_tiles_pallas_epilogue): the derived
    group never takes a lane of the contraction, and only the candidate
    tables + the computed planes leave VMEM; every other backend runs the
    SAME jnp ops on the tile it built — bit-identical tables by
    construction (the parity suite pins it).

    Args mirror histogram_tiles plus the epilogue pack (see
    histogram_tiles_pallas_epilogue). Returns (tile [2P, F, B, S] f32,
    cand [2P, F, CAND_CHANNELS]): the computed leaves, then the derived.
    """
    from . import pallas_hist

    p = sel.shape[0]
    s = stats.shape[1]
    if epilogue_supported(method, binsT, p, s, dtype, interpret):
        if gather_idx is not None:
            _, binsT, stats, leaf_ids = gather_rows(bins, binsT, stats,
                                                    leaf_ids, gather_idx)
        return pallas_hist.histogram_tiles_pallas_epilogue(
            binsT, stats, leaf_ids, sel, sel_derived, parent_planes,
            leaf_aux, fmeta, pvec, num_bins,
            block=block or pallas_hist.DEFAULT_BLOCK,
            mode=_KERNEL_MODE[method],
            interpret=interpret and jax.default_backend() != "tpu",
            with_monotone=with_monotone, q_scale=q_scale)

    # XLA twin: build the computed slots' planes with the requested
    # backend, then the identical derive + scan at plane level
    tile = histogram_tiles(bins, stats, leaf_ids, sel, num_bins,
                           method=method, block=block, dtype=dtype,
                           binsT=binsT, gather_idx=gather_idx,
                           interpret=interpret)
    return derive_and_scan(tile, sel_derived, parent_planes, leaf_aux,
                           fmeta, pvec, q8=method.endswith("_q8"),
                           q_scale=q_scale, with_monotone=with_monotone)


@jax.named_scope("split_search")
def derive_and_scan(tile, sel_derived, parent_planes, leaf_aux, fmeta, pvec,
                    *, q8: bool = False, q_scale=None,
                    with_monotone: bool = False):
    """The XLA twin of the in-kernel split epilogue, at plane level:
    dequantize the P computed planes (q8, fenced), derive slot q's
    sibling as parent[q] - computed[q] where ``sel_derived[q]`` names one
    (the kernel's second lane group), scan all 2P leaves to their best
    per-feature candidates. The grower calls this ONCE per tile pass,
    OUTSIDE the compaction-rung lax.cond — the rung branches return only
    the tile, so the scan compiles once per grower instead of once per
    rung. Returns (planes [2P, F, B, S], cand [2P, F, CAND_CHANNELS])."""
    from . import pallas_hist
    from .split import _round_fence, numerical_candidates

    params = pallas_hist._epilogue_params(pvec.astype(jnp.float32))
    if q8:
        # fence the dequant product before the sibling subtraction —
        # same reason as the kernel epilogue (see _epilogue_feature):
        # an FMA-contracted multiply-sub would break ladder invariance
        tile = _round_fence(
            tile.astype(jnp.float32) * q_scale[None, None, None, :],
            params)
    else:
        tile = tile.astype(jnp.float32)
    full = jnp.concatenate([tile, pallas_hist.derived_planes(
        tile, sel_derived, parent_planes.astype(jnp.float32))])
    la = leaf_aux.astype(jnp.float32).reshape(-1, leaf_aux.shape[-1])
    fm = fmeta.astype(jnp.float32)
    cand = numerical_candidates(
        full, la[:, 0], la[:, 1], la[:, 2], la[:, 3],
        fm[:, 0].astype(jnp.int32), fm[:, 1].astype(jnp.int32),
        fm[:, 2].astype(jnp.int32), fm[:, 3].astype(jnp.int32),
        params, with_monotone=with_monotone,
        leaf_min=la[:, 4], leaf_max=la[:, 5])
    return full, cand

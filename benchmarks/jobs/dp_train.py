"""Job ``dp_train``: lgb.Dataset -> lgb.train under ``tree_learner=data``
over the chips of one host, timed per iteration.

Job ``train``'s loop, window and stamps (it calls them), for a job whose
rows are sharded over a device mesh: every histogram pass ends in a
reduce-scatter of the tiles over feature ownership and every search round
in a best-split sync. What differs from ``train``: the data has missing
values; the plain reference is ``reference_dp.py``; ``check`` reads every
chip's OWN rows back from that chip and holds them, and tree 0, to it at
full size: the rows are partitioned, the root split is the whole data's,
every leaf count is a traversal's of the raw values (a NaN by the printed
default direction; equal up to the float32 rounding of a count over 2^24)
and every leaf value the float64 sums over its rows; the construct's shard placement and the collectives' counters are
read from the library's own surface (``Dataset.construct_stats``,
``Dataset.row_bins``, ``GBDT.coll_bytes_total`` /
``split_sync_calls_total``), and a traced run has to show the kernel and a
collective on every device plane.

Before any device work ``setup`` asks the library for what this cell
reads: a library without it cannot report what the cell lists, and the run
ends there, within seconds, instead of compiling for minutes.
"""

import os
import time

import numpy as np

import reference
import reference_dp
import reference_sparse
import trace_reduce
from jobs import train as train_job

# what check and the layer metrics read of Dataset.construct_stats
STATS = ("shard_rows_min", "shard_rows_max", "shard_place_s")
REDUCTIONS = ("reduce-scatter", "all-reduce")
THREADS = max(1, min(16, (os.cpu_count() or 2) - 1))


def _surface() -> None:
    """An error where the library lacks what this job reads."""
    import lightgbm_tpu as lgb
    from lightgbm_tpu.models.gbdt import GBDT
    from lightgbm_tpu.utils import profiling
    if (not hasattr(lgb.Dataset, "row_bins")
            or not hasattr(GBDT, "split_sync_calls_total")
            or not {"hist_allreduce", "split_sync"} <= set(profiling.SCOPES)):
        raise RuntimeError(
            "this lightgbm_tpu has no Dataset.row_bins, no "
            "GBDT.split_sync_calls_total or no hist_allreduce / split_sync "
            "scope: it cannot report what the cell lists")


def _chip_peaks() -> list:
    """The most of its memory each chip ever took (``run.device_memory``'s
    rule: the larger of in use and reserved), in device order."""
    import jax
    stats = [d.memory_stats() or {} for d in jax.devices()]
    return [max(s.get("peak_bytes_in_use", 0),
                s.get("peak_bytes_reserved", 0)) for s in stats]


def _own_rows(ds) -> list:
    """[(device, first global row, bins ``[rows, F]`` as read back from
    that chip)] of the training set's shards, in row order, the padding
    rows past ``num_data`` cut off."""
    out = []
    for sh in ds.row_bins.addressable_shards:
        a = sh.index[0].start or 0
        rows = max(0, min(sh.data.shape[0], ds.num_data - a))
        out.append((sh.device, a, np.asarray(sh.data)[:rows]))
    return sorted(out, key=lambda s: s[1])


def setup(ctx) -> dict:
    import jax
    import lightgbm_tpu as lgb
    from lightgbm_tpu import binning
    _surface()
    cfg, params = ctx.cfg, train_job._params(ctx)
    rows, valid_rows = int(cfg["data"]["rows"]), int(cfg["assumed"]["valid_rows"])
    with ctx.phase("datagen"):
        X, y = ctx.data.make(cfg["data"], ctx.seed, rows + valid_rows, rows)
    st = {"X": X[:rows], "y": y[:rows], "Xv": X[rows:], "yv": y[rows:],
          "params": params, "parallel": True}
    ctx.log(f"datagen: {ctx.phases['datagen']:.1f} s {X.dtype} {X.shape} "
            f"positives={float(st['y'].mean()):.4f} missing by column="
            f"{np.isnan(st['X'][:200000]).mean(axis=0).round(3).tolist()}")

    with ctx.phase("construct"):
        ds = lgb.Dataset(st["X"], label=st["y"], params=params)
        ds.construct()
        if ds.row_bins is None:
            raise RuntimeError("the construct left no row shards: "
                               f"{len(jax.devices())} devices, params "
                               f"{params}")
        jax.block_until_ready(ds.row_bins)
    stats = dict(ds.construct_stats or {})
    missing = [k for k in STATS if k not in stats]
    if missing:
        raise RuntimeError(f"Dataset.construct_stats lacks {missing}")
    ctx.phases["shard_place"] = float(stats["shard_place_s"])

    first = min(ds.row_bins.addressable_shards,
                key=lambda sh: sh.index[0].start or 0)
    k = min(rows, 4096, first.data.shape[0])
    used = [ds.mappers[j] for j in ds.used_features]
    host = binning.bin_data(
        st["X"][:k][:, ds.used_features].astype(np.float64), used)
    st["construct_ok"] = bool(np.array_equal(
        np.asarray(first.data[:k]).astype(np.int32), host))
    st["binned_on_device"] = bool(ds.binned_on_device)
    ctx.log(f"construct: {ctx.phases['construct']:.1f} s rows={ds.num_data} "
            f"columns={ds.num_used_features()} bins={ds.max_num_bins} "
            f"stats={stats} on_device_quantiser={ds.binned_on_device} "
            f"host_slice_equal={st['construct_ok']} shards="
            f"{[(str(s.device), s.data.shape) for s in ds.row_bins.addressable_shards]} "
            f"whole_matrix_held={ds._bins is not None} "
            f"chip_peak_bytes={_chip_peaks()}")
    st["ds"] = ds
    return st


def window(ctx, st: dict, seconds: float, trace) -> dict:
    res = train_job.window(ctx, st, seconds, trace)
    gb = st["booster"]._boosting
    # the program's collective counters, over ALL the run's iterations
    # (warm-up included: every pass moves the same bytes), scaled to the
    # window's so that the counter reader's division gives one iteration's
    per_iter = ctx.units / max(gb.iter, 1)
    ctx.counters["hist_coll_bytes"] = gb.coll_bytes_total * per_iter
    ctx.counters["split_sync_calls"] = gb.split_sync_calls_total * per_iter
    peaks = _chip_peaks()
    ctx.counters["chip_peak_bytes_max"] = float(max(peaks))
    ctx.counters["chip_peak_bytes_min"] = float(min(peaks))
    ctx.log(f"data_parallel: coll_bytes_per_iter="
            f"{gb.coll_bytes_total / max(gb.iter, 1):.0f} "
            f"split_sync_calls_per_iter="
            f"{gb.split_sync_calls_total / max(gb.iter, 1):.2f} over "
            f"{gb.iter} iterations; chip_peak_bytes={peaks} "
            f"(fullest / emptiest {max(peaks) / max(min(peaks), 1):.4f}); "
            f"whole_matrix_held={st['ds']._bins is not None}")
    return res


def _tables(ds):
    """Per used column what the reference needs of the quantiser: the
    finite bin upper bounds, the count of value bins, the NaN bin or -1."""
    from lightgbm_tpu import binning
    bounds, real, nan_bin = [], [], []
    for j in ds.used_features:
        m = ds.mappers[int(j)]
        has_nan = m.missing_type == binning.MISSING_NAN
        r = m.num_bin - (1 if has_nan else 0)
        bounds.append(np.asarray(m.bin_upper_bound[:r - 1], np.float64))
        real.append(r)
        nan_bin.append(m.num_bin - 1 if has_nan else -1)
    return bounds, real, nan_bin


def _planes(ctx, bad, want: int) -> None:
    """The kernel and a histogram reduction on every device plane."""
    seen = {}
    for name, evs in ctx.view.devices.items():
        seen[name] = (trace_reduce.sum_matching(evs, ["hist_tiles"]),
                      trace_reduce.sum_matching(evs, REDUCTIONS),
                      ctx.view.busy[name])
    ctx.log("planes: " + "; ".join(
        f"{n} hist_tiles={k:.6f} s reductions={c:.6f} s busy={b:.6f} s"
        for n, (k, c, b) in sorted(seen.items())))
    if len(seen) != want:
        bad.append(f"the trace holds {len(seen)} device planes, want {want}")
    for n, (k, c, _b) in sorted(seen.items()):
        if not k > 0:
            bad.append(f"no hist_tiles kernel event on {n}")
        if not c > 0:
            bad.append(f"no reduce-scatter or all-reduce event on {n}")


def check(ctx, st: dict) -> list:
    """Reasons why the run is not correct (empty: correct)."""
    import jax
    from lightgbm_tpu.config import Config
    bad = list(st["degraded"])
    booster, ds, params = st["booster"], st["ds"], st["params"]
    gb = booster._boosting
    on_chip = jax.default_backend() == "tpu"
    conf = Config.from_params(params)
    min_data = float(conf.min_data_in_leaf)
    min_hess = float(conf.min_sum_hessian_in_leaf)
    X, y, n = st["X"], st["y"], len(st["y"])
    if not st["construct_ok"]:
        bad.append("device bins differ from the host quantiser")
    if st["binned_on_device"] != on_chip:
        bad.append(f"on-device quantiser ran={st['binned_on_device']} on "
                   f"backend {jax.default_backend()!r}")

    # the mesh, and where the bins lie
    want = int(ctx.cell["chips"])
    pg = gb._parallel_grower
    hm = gb._hist_method()
    dbins = gb._fused_parallel_bindings(hm)["bins"]
    span = len(dbins.sharding.device_set)
    held = len(ds.row_bins.sharding.device_set)
    ctx.log(f"mesh: devices={pg.ndev} step_bins_on={span} per_device="
            f"{dbins.addressable_shards[0].data.shape} dataset_shards_on="
            f"{held} whole_matrix_held={ds._bins is not None}")
    if (pg.ndev, span, held) != (want, want, want):
        bad.append(f"mesh has {pg.ndev} devices, the step's bins lie on "
                   f"{span}, the data set's on {held}, want {want}")
    if ds._bins is not None:
        bad.append("the training set holds a whole bin matrix beside its "
                   "row shards")

    # (a) every chip's own rows, read back from it, against the whole host
    # bin matrix: the rows are partitioned
    t0 = time.time()
    bounds, real, nan_bin = _tables(ds)
    cols = ds.used_features
    B = int(ds.max_num_bins)
    hostT = reference_dp.host_bins(X[:, cols] if len(cols) != X.shape[1]
                                   else X, bounds, nan_bin, THREADS)
    cnt, ysum = reference_dp.column_histograms(hostT, y, B, THREADS)
    del hostT
    own = _own_rows(ds)
    shard_cnt, shard_ysum, rows_by_chip = [], [], []
    for _dev, a, b in own:
        c_, y_ = reference_dp.column_histograms(
            np.ascontiguousarray(b.T), y[a:a + len(b)], B, THREADS)
        shard_cnt.append(c_)
        shard_ysum.append(y_)
        rows_by_chip.append(len(b))
    del own
    off = reference_dp.partition_faults(shard_cnt, cnt)
    y_off = float(np.abs(sum(shard_ysum) - ysum).max())
    ctx.log(f"partition: rows by chip {rows_by_chip} (sum "
            f"{sum(rows_by_chip)} of {n}); the chips' count histograms "
            f"summed differ from the whole host matrix's by {off} rows, "
            f"their label sums by {y_off:.3e} ({time.time() - t0:.1f} s)")
    if sum(rows_by_chip) != n or off:
        bad.append(f"the chips' own rows are no partition of the data: "
                   f"{sum(rows_by_chip)} rows held, histograms {off} rows "
                   f"off the whole host matrix's")
    if not y_off <= 1e-6:
        bad.append(f"the chips' label sums by bin differ from the whole "
                   f"matrix's by {y_off:.3e}")

    # (b) the root split of tree 0 against the search over all rows
    t0 = time.time()
    gain, f_np, t_np, nan_left, left_np = reference_dp.root_split(
        cnt, ysum, y, real, nan_bin, min_data, min_hess)
    text = booster.model_to_string(num_iteration=1)
    tree = reference.parse_model(text)[0]
    dtype = reference_sparse.tree_field(text, 0, "decision_type").astype(int)
    f_sys, thr = int(tree["split_feature"][0]), float(tree["threshold"][0])
    gain_sys, left_raw = reference_dp.gain_of_raw_split(
        X[:, f_sys], y, thr, dtype[0], min_data, min_hess)
    left_sys = reference.child_count(tree, int(tree["left_child"][0]))
    right_sys = reference.child_count(tree, int(tree["right_child"][0]))
    short = (gain - gain_sys) / gain
    tol = float(ctx.cell["root_gain_rel_tolerance"])
    ctx.log(f"root_split: numpy best column={int(cols[f_np])} bin<={t_np} "
            f"nan_left={nan_left} gain={gain:.6f} left={left_np}; system "
            f"column={f_sys} x<={thr!r} decision_type={dtype[0]} "
            f"gain(numpy)={gain_sys:.6f} shortfall={short:.3e} (tolerance "
            f"{tol}) counts system={left_sys}/{right_sys} numpy="
            f"{left_raw}/{n - left_raw} ({time.time() - t0:.1f} s)")
    if not short <= tol:
        bad.append(f"root split gain falls short of numpy's by {short:.3e}")
    ctol = int(ctx.cell["root_count_abs_tolerance"])
    if left_sys + right_sys != n or abs(left_sys - left_raw) > ctol:
        bad.append(f"root child counts {left_sys}/{right_sys} differ from "
                   f"numpy's {left_raw}/{n - left_raw} by more than {ctol}")

    # (c), (d) every leaf of tree 0: its count against a traversal of the
    # raw values, its value against float64 sums over its rows
    t0 = time.time()
    leaf = reference_dp.leaf_index(tree, dtype, X, THREADS)
    ref_counts = np.bincount(leaf, minlength=tree["num_leaves"])
    off = int(np.abs(ref_counts - tree["leaf_count"]).sum())
    ref_values = reference_sparse.leaf_values(
        tree, leaf, y,
        float(reference_sparse.tree_field(text, 0, "shrinkage")[0]),
        float(params.get("lambda_l2", 0.0)))
    err = np.abs(tree["leaf_value"] - ref_values)
    mid, worst = float(np.median(err)), float(err.max())
    vtol = float(ctx.cell["leaf_value_median_abs_tolerance"])
    missing_nodes = int(((dtype >> reference_dp.MISSING_SHIFT) & 3
                         == reference_dp.MISSING_NAN).sum())
    ctx.log(f"leaves: {tree['num_leaves']} leaves, "
            f"{int(tree['leaf_count'].sum())} rows, {missing_nodes} nodes "
            f"on a column with missing values; sum |model - raw traversal| = {off} (tolerance {ctx.cell['leaf_count_sum_abs_tolerance']}); leaf values "
            f"against float64 sums: median |difference| {mid:.3e} "
            f"(tolerance {vtol}), largest {worst:.3e} "
            f"({time.time() - t0:.1f} s)")
    ltol = int(ctx.cell["leaf_count_sum_abs_tolerance"])
    if off > ltol or int(tree["leaf_count"].sum()) != n:
        bad.append(f"leaf counts differ from the raw traversal's by {off} "
                   f"in all, more than {ltol}")
    if not mid <= vtol:
        bad.append(f"the leaf values are {mid:.3e} off the float64 sums "
                   f"over their rows, by the median")

    # (e) quality anchor
    if not st["auc"] >= float(ctx.cell["auc_anchor"]):
        bad.append(f"valid AUC {st['auc']:.6f} below the anchor")
    # nothing compiled inside the window; the kernel is in the step and,
    # in a traced run, on every plane beside a reduction
    if st["compiles_in_window"]:
        bad.append(f"{st['compiles_in_window']} compile requests inside "
                   f"the window")
    kernels = train_job._kernels_in_program(gb, hm)
    ctx.log(f"kernels_in_program={sorted(set(kernels))} x{len(kernels)} "
            f"method={hm}")
    if not hm.startswith("pallas"):
        bad.append(f"executed histogram method {hm!r} is not a Pallas kernel")
    if on_chip and not any(k.startswith("hist_tiles") for k in kernels):
        bad.append("no compiled hist_tiles kernel in the fused step")
    if not on_chip and not gb._hist_interpret():
        bad.append("off the chip the kernels must run interpreted")
    if on_chip and ctx.view is not None:
        _planes(ctx, bad, want)
    return bad

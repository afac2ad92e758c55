"""Job ``missing_train``: lgb.Dataset over a wide dense matrix whose
entries are mostly NaN -> lgb.train, timed per iteration.

Job ``train``'s loop, window and stamps (it calls them, as ``wide_train``
does), for a job whose new thing on the hot path is MISSING VALUES at
width: every column's histogram carries a NaN bin, and the split search
that runs in the histogram kernel's epilogue scans every column both ways
(NaN left, NaN right) in feature blocks whose last one is padded. The
plain reference is ``reference_missing.py``, float64 numpy over the RAW
values that never sees a kernel, a tile, a block or the scan metadata.
``check`` holds tree 0 to it at full size (the root split over ALL
columns and its default direction, every leaf count exactly, every leaf
value) and then grows ONE more tree, by the step the window timed, on a
label that only three designated columns of every feature block explain:
each has to be cut at the planted bound, with NaN on the planted side.
The plan the run took is compared with the cell's record.

A traced run keeps the library's TIMETAG split counters (models/gbdt.py
``TREE_COUNTERS``: splits on a column with a NaN bin, those that send NaN
left, leaves, trees) over the run's trees and holds them to the model
text; an untraced run leaves TIMETAG off and pays nothing for them.

Before any device work ``setup`` asks the library for what this cell
reads: a library without it cannot report what the cell lists, and the
run ends there, within seconds.
"""

import os
import time

import numpy as np

import reference
import reference_dp
import reference_missing
import reference_sparse
import reference_wide
from jobs import train as train_job

THREADS = max(1, min(16, (os.cpu_count() or 2) - 1))
# the plan's fields the cell records and check compares
PLAN = ("feature_block", "feature_blocks", "compaction_ladder",
        "split_fusion")
# the library's TIMETAG counters this job reads, and what it names them
COUNTERS = ("splits", "nan_splits", "nan_left_splits", "tree_leaves",
            "trees")


def _surface() -> None:
    """An error where the library lacks what this job reads."""
    import lightgbm_tpu as lgb
    from lightgbm_tpu.models import gbdt
    from lightgbm_tpu.ops import pallas_hist
    if (not hasattr(pallas_hist, "feature_block")
            or not hasattr(lgb.Booster, "hist_plan")
            or not set(COUNTERS) <= set(getattr(gbdt, "TREE_COUNTERS",
                                                ()))):
        raise RuntimeError(
            "this lightgbm_tpu has no pallas_hist.feature_block, no "
            "Booster.hist_plan or no split counters for columns with "
            "missing values (gbdt.TREE_COUNTERS): it cannot report what "
            "the cell lists")


def setup(ctx) -> dict:
    import jax
    import lightgbm_tpu as lgb
    from lightgbm_tpu import binning
    from lightgbm_tpu.config import Config
    _surface()
    cfg, params = ctx.cfg, train_job._params(ctx)
    rows, valid_rows = int(cfg["data"]["rows"]), int(cfg["data"]["valid_rows"])
    conf = Config.from_params(params)
    with ctx.phase("datagen"):
        # the positions the library samples keep their rows on every seed
        keep = binning.sample_indices(rows, conf.bin_construct_sample_cnt,
                                      conf.data_random_seed)
        X, y = ctx.data.make(cfg["data"], ctx.seed, rows + valid_rows, rows,
                             keep=keep)
    st = {"X": X[:rows], "y": y[:rows], "Xv": X[rows:], "yv": y[rows:],
          "params": params, "parallel": False}
    nan = np.isnan(st["X"][:20000]).mean(axis=0)
    ctx.log(f"datagen: {ctx.phases['datagen']:.1f} s {X.dtype} {X.shape} "
            f"positives={float(st['y'].mean()):.5f} "
            f"({int(st['y'].sum())} rows) NaN share "
            f"{float(nan.mean()):.4f}, by column "
            f"{np.quantile(nan, [0, 0.25, 0.5, 0.75, 1]).round(4).tolist()}")

    with ctx.phase("construct"):
        ds = lgb.Dataset(st["X"], label=st["y"], params=params)
        ds.construct()
        jax.block_until_ready(ds.bins)
    k = min(rows, 4096)
    used = [ds.mappers[j] for j in ds.used_features]
    host = binning.bin_data(
        st["X"][:k][:, ds.used_features].astype(np.float64), used)
    st["construct_ok"] = bool(np.array_equal(
        np.asarray(ds.bins[:k]).astype(np.int32), host))
    st["slice_nan_rows"] = int(np.isnan(st["X"][:k]).any(axis=1).sum())
    st["binned_on_device"] = bool(ds.binned_on_device)
    bins = np.array([m.num_bin for m in used])
    with_nan = sum(m.missing_type == binning.MISSING_NAN for m in used)
    ctx.log(f"construct: {ctx.phases['construct']:.1f} s rows={ds.num_data} "
            f"columns={ds.num_used_features()} of {X.shape[1]}, "
            f"{with_nan} with a NaN bin; bins={ds.max_num_bins} (by "
            f"column {np.quantile(bins, [0, 0.25, 0.5, 0.75, 1]).tolist()}) "
            f"on_device_quantiser={ds.binned_on_device} host_slice_equal="
            f"{st['construct_ok']} ({st['slice_nan_rows']} of its {k} rows "
            f"hold a NaN)")
    st["ds"] = ds

    with ctx.phase("tune"):
        plan = lgb.Booster(params=params, train_set=ds).hist_plan()
    st["plan"] = plan
    ctx.log(f"tune: {ctx.phases['tune']:.1f} s -> {plan}")
    return st


def window(ctx, st: dict, seconds: float, trace) -> dict:
    from lightgbm_tpu.utils import profiling
    was = profiling.enabled()
    before = profiling.counters()
    if trace:
        profiling.enable(True)
    try:
        res = train_job.window(ctx, st, seconds, trace)
        # the model text fetches every tree still pending
        st["text"] = st["booster"].model_to_string()
    finally:
        profiling.enable(was)
    after = profiling.counters()
    if trace:
        took = {k: after.get(k, 0.0) - before.get(k, 0.0) for k in COUNTERS}
        st["counted"] = took
        ctx.counters.update(took)
        ctx.log(f"split counters over the run's {took['trees']:.0f} trees: "
                f"{took}")
    return res


def _model_counts(text: str) -> dict:
    """What the counters must read, from the model text: splits, those on
    a column with a NaN bin (missing type NaN), those that send NaN left,
    leaves and trees."""
    trees = reference.parse_model(text)
    out = dict.fromkeys(COUNTERS, 0)
    for k, t in enumerate(trees):
        n = t["num_leaves"] - 1
        out["trees"] += 1
        out["tree_leaves"] += t["num_leaves"]
        out["splits"] += n
        if n:
            dt = reference_sparse.tree_field(text, k,
                                             "decision_type").astype(int)
            nan = ((dt >> reference_dp.MISSING_SHIFT) & 3
                   ) == reference_dp.MISSING_NAN
            left = (dt & reference_dp.DEFAULT_LEFT) != 0
            out["nan_splits"] += int(nan.sum())
            out["nan_left_splits"] += int((nan & left).sum())
    return out


def _root(ctx, bad, name, tree, dtype, X, y, hists, tabs, min_data,
          min_hess):
    """The root split of a first tree against the search over ALL
    columns, NaN tried on both sides: the gain within the cell's
    tolerance, the child counts exact, and the printed default direction
    the better one wherever the two directions' gains differ by more than
    the tolerance."""
    t0 = time.time()
    n = len(y)
    bounds, real, nan_bin = tabs
    gain, f_np, t_np, nan_left, left_np = reference_dp.root_split(
        *hists, y, real, nan_bin, min_data, min_hess)
    f_sys, thr = int(tree["split_feature"][0]), float(tree["threshold"][0])
    (g_left, c_left), (g_right, c_right) = reference_missing.direction_gains(
        X[:, f_sys], y, thr, min_data, min_hess)
    sys_left = bool(int(dtype[0]) & reference_dp.DEFAULT_LEFT)
    gain_sys, left_raw = (g_left, c_left) if sys_left else (g_right, c_right)
    left_sys = reference.child_count(tree, int(tree["left_child"][0]))
    right_sys = reference.child_count(tree, int(tree["right_child"][0]))
    short = (gain - gain_sys) / gain
    tol = float(ctx.cell["root_gain_rel_tolerance"])
    apart = abs(g_left - g_right) / gain
    ctx.log(f"root_split[{name}]: numpy best column={f_np} bin<={t_np} "
            f"nan_left={nan_left} gain={gain:.6f} left={left_np} over "
            f"{len(real)} columns; system column={f_sys} x<={thr!r} "
            f"nan_left={sys_left} gain(numpy)={gain_sys:.6f} "
            f"shortfall={short:.3e} (tolerance {tol}); the other direction "
            f"{g_right if sys_left else g_left:.6f} ({apart:.3e} apart) "
            f"counts system={left_sys}/{right_sys} numpy="
            f"{left_raw}/{n - left_raw} ({time.time() - t0:.1f} s)")
    if not short <= tol:
        bad.append(f"{name}: root split gain falls short of numpy's by "
                   f"{short:.3e}")
    if (left_sys, right_sys) != (left_raw, n - left_raw):
        bad.append(f"{name}: root child counts {left_sys}/{right_sys} "
                   f"differ from numpy's {left_raw}/{n - left_raw}")
    if apart > tol and sys_left != (g_left > g_right):
        bad.append(f"{name}: the root sends NaN "
                   f"{'left' if sys_left else 'right'}, the other way "
                   f"gains {apart:.3e} more")


def _leaves(ctx, bad, name, text, tree, dtype, X, y, params, tol=None):
    """Every leaf of a first tree against a traversal of the raw values (a
    NaN by the printed direction): the counts exactly, and the printed
    values against float64 sums over the leaf's rows (the MEDIAN
    difference limited where ``tol`` is given)."""
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
    ctx.log(f"leaves[{name}]: {tree['num_leaves']} leaves, "
            f"{int(tree['leaf_count'].sum())} rows, smallest "
            f"{int(tree['leaf_count'].min())}; sum |model - raw traversal| "
            f"= {off} (exact wanted); leaf values against float64 sums: "
            f"median |difference| {mid:.3e} (tolerance {tol}), largest "
            f"{worst:.3e} ({time.time() - t0:.1f} s)")
    if off or int(tree["leaf_count"].sum()) != len(y):
        bad.append(f"{name}: leaf counts differ from the raw traversal's "
                   f"by {off} in all")
    if tol is not None and not mid <= tol:
        bad.append(f"{name}: the leaf values are {mid:.3e} off the float64 "
                   f"sums over their rows, by the median")


def _probe(ctx, bad, st, X, binsT, tabs, min_data, min_hess):
    """One tree by the step the window timed, on the planted label over
    three columns of every feature block."""
    import jax
    import lightgbm_tpu as lgb
    from lightgbm_tpu import compile_cache
    ds, params, plan = st["ds"], st["params"], st["plan"]
    bounds, real, nan_bin = tabs
    t0 = time.time()
    f = len(bounds)
    cols = reference_wide.designated_columns(f, plan["feature_block"] or f)
    # a slice of eight times the rows a child needs at a hessian of 1/4 a
    # row (tree 0 of a label near one half)
    slice_rows = int(8 * min_hess / 0.25)
    y2, steps = reference_missing.planted_list(binsT, cols, real,
                                               slice_rows, ctx.seed)
    built = compile_cache.module_count("misses", "jit(_fused_step)")
    ds.set_label(y2)
    try:
        probe = lgb.train(params, ds, num_boost_round=1,
                          keep_training_booster=True)
        jax.block_until_ready(probe._boosting.train_score)
    finally:
        ds.set_label(st["y"])
    built = compile_cache.module_count("misses", "jit(_fused_step)") - built
    text = probe.model_to_string()
    tree = reference.parse_model(text)[0]
    dtype = reference_sparse.tree_field(text, 0, "decision_type").astype(int)
    found = reference_missing.planted_found(tree, dtype, steps, bounds, X)
    took = [r for _, r, _, _, _ in steps]
    held = [ok for node, ok in found if node >= 0 and ok is not None]
    ctx.log(f"probe: {len(cols)} designated columns in "
            f"{plan['feature_blocks']} feature blocks of "
            f"{plan['feature_block']} ({cols.tolist()}); a step takes "
            f"{min(took)}..{max(took)} rows, label 1 in "
            f"{float(y2.mean()):.4f}; one tree of {tree['num_leaves']} "
            f"leaves cuts {sum(node >= 0 for node, _ in found)} of them at "
            f"the planted bound, {sum(held)} of {len(held)} whose node "
            f"{reference_missing.NAN_ROWS_HELD} or more NaN rows reach "
            f"with NaN on the planted side "
            f"(step: column, rows, mean, bound bin, NaN left -> node, "
            f"direction right: {[(*s, *r) for s, r in zip(steps, found)]}); "
            f"fused steps built for it: {built} ({time.time() - t0:.1f} s)")
    if built and jax.default_backend() == "tpu":
        bad.append("the probe tree was grown by another step than the one "
                   "the window timed")
    missing = [s[0] for s, (node, _) in zip(steps, found) if node < 0]
    if missing:
        bad.append(f"the probe tree does not cut columns {sorted(missing)} "
                   f"at the planted bound")
    wrong = [s[0] for s, (_, ok) in zip(steps, found) if ok is False]
    if wrong:
        bad.append(f"the probe tree sends NaN to the wrong side at columns "
                   f"{sorted(wrong)}")
    held_sides = {s[4] for s, (_, ok) in zip(steps, found) if ok}
    for side in sorted({True, False} - held_sides):
        bad.append(f"the probe holds no cut that sends NaN "
                   f"{'left' if side else 'right'}: no designated column "
                   f"of that side was cut where NaN rows reach")
    cnt2, ysum2 = reference_wide.column_histograms(
        binsT, y2, int(ds.max_num_bins), THREADS)
    _root(ctx, bad, "probe", tree, dtype, X, y2, (cnt2, ysum2), tabs,
          min_data, min_hess)
    _leaves(ctx, bad, "probe", text, tree, dtype, X, y2, params)


def check(ctx, st: dict) -> list:
    """Reasons why the run is not correct (empty: correct)."""
    import jax
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.ops import pallas_hist
    t_check = time.time()
    bad = list(st["degraded"])
    booster, ds, params = st["booster"], st["ds"], st["params"]
    gb = booster._boosting
    on_chip = jax.default_backend() == "tpu"
    conf = Config.from_params(params)
    min_data = float(conf.min_data_in_leaf)
    min_hess = float(conf.min_sum_hessian_in_leaf)
    if not st["construct_ok"]:
        bad.append("device bins differ from the host quantiser")
    if not st["slice_nan_rows"]:
        bad.append("the quantiser's slice compared holds no NaN")
    if st["binned_on_device"] != on_chip:
        bad.append(f"on-device quantiser ran={st['binned_on_device']} on "
                   f"backend {jax.default_backend()!r}")
    if len(ds.used_features) != st["X"].shape[1]:
        bad.append(f"{st['X'].shape[1] - len(ds.used_features)} columns "
                   f"are trivial: the device matrix is narrower than the "
                   f"data")
        return bad
    X, y = st["X"], st["y"]

    # (a) the root of tree 0 over ALL columns, (b) every leaf count, (c)
    # every leaf value, from the raw values and the quantiser's bounds
    t0 = time.time()
    B = int(ds.max_num_bins)
    tabs = reference_missing.tables(ds.mappers)
    binsT, cnt, ysum = reference_missing.whole_histograms(
        X, y, tabs[0], tabs[2], B, THREADS)
    ctx.log(f"reference: bins and histograms of {X.shape} raw values, "
            f"{int(np.isnan(X[:20000]).sum())} NaN in the first 20,000 "
            f"rows ({time.time() - t0:.1f} s)")
    text = booster.model_to_string(num_iteration=1)
    tree = reference.parse_model(text)[0]
    dtype = reference_sparse.tree_field(text, 0, "decision_type").astype(int)
    _root(ctx, bad, "tree 0", tree, dtype, X, y, (cnt, ysum), tabs,
          min_data, min_hess)
    _leaves(ctx, bad, "tree 0", text, tree, dtype, X, y, params,
            float(ctx.cell["leaf_value_median_abs_tolerance"]))
    fb = st["plan"]["feature_block"] or len(tabs[0])
    on_nan = ((dtype >> reference_dp.MISSING_SHIFT) & 3
              ) == reference_dp.MISSING_NAN
    ctx.log(f"tree 0 splits on {len(set(tree['split_feature'].tolist()))} "
            f"columns of feature blocks "
            f"{sorted({int(j) // fb for j in tree['split_feature']})}; "
            f"{int(on_nan.sum())} of its {tree['num_leaves'] - 1} splits on "
            f"a column with a NaN bin, "
            f"{int((on_nan & ((dtype & 2) != 0)).sum())} of them NaN left")
    # (d) the probe, which holds every feature block
    _probe(ctx, bad, st, X, binsT, tabs, min_data, min_hess)
    del binsT

    # (e) quality anchor
    if not st["auc"] >= float(ctx.cell["auc_anchor"]):
        bad.append(f"valid AUC {st['auc']:.6f} below the anchor")
    # (f) nothing compiled inside the window; the epilogue kernel is in
    # the step; the plan is the one the cell records; the counters are
    # the model text's
    if st["compiles_in_window"]:
        bad.append(f"{st['compiles_in_window']} compile requests inside "
                   f"the window")
    hm = gb._hist_method()
    kernels = train_job._kernels_in_program(gb, hm)
    ctx.log(f"kernels_in_program={sorted(set(kernels))} x{len(kernels)} "
            f"method={hm}")
    if not hm.startswith("pallas"):
        bad.append(f"executed histogram method {hm!r} is not a Pallas kernel")
    if on_chip and not any(k.startswith(pallas_hist.EPILOGUE_KERNEL_NAME)
                           for k in kernels):
        bad.append("no compiled hist_tiles_split_epilogue kernel in the "
                   "fused step")
    if not on_chip and not gb._hist_interpret():
        bad.append("off the chip the kernels must run interpreted")
    took = {k: st["plan"][k] for k in PLAN}
    if booster.hist_plan() != st["plan"]:
        bad.append(f"the plan moved during the run: {st['plan']} before, "
                   f"{booster.hist_plan()} after")
    want = ctx.cell.get("plan")
    if on_chip and took != want:
        bad.append(f"the run took the plan {took}, the cell records {want}")
    if "counted" in st:
        want = _model_counts(st["text"])
        got = {k: int(v) for k, v in st["counted"].items()}
        if got != want:
            bad.append(f"the library's split counters read {got}, the "
                       f"model text holds {want}")
    ctx.log(f"check: {time.time() - t_check:.1f} s after the window")
    return bad

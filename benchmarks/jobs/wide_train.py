"""Job ``wide_train``: lgb.Dataset over a dense matrix of thousands of
columns -> (tune) -> lgb.train, timed per iteration.

Job ``train``'s loop, window and stamps (it calls them), for a job whose
only new thing on the hot path is WIDTH: the histogram kernel walks the
device columns in feature blocks (``pallas_hist.feature_block``), and
everything the grower keeps a leaf and a column (the ``[L, F, B, 3]``
state, the tiles, the parent planes, the candidate tables) is two
thousand columns wide. What differs from ``train``: the plain reference is
``reference_wide.py``, float64 numpy over the RAW values that never sees a
kernel, a tile or a block; ``check`` holds tree 0 to it at full size (the
root split over ALL columns, every leaf count exactly, every leaf value)
and then grows ONE more tree, by the step the window timed, on a label
that only designated columns explain: three columns of every feature block
the plan reports, column 0 and the last column among them. That probe
tree has to split every designated column at the planted bound, so no
feature block's planes, parent planes or candidates can be wrong and the
run still be correct. The plan the run took is compared with the cell's
record.

Before any device work ``setup`` asks the library for what this cell
reads: a library without it cannot report what the cell lists (and would
compile a kernel unrolled over every column for an hour), and the run ends
there, within seconds.
"""

import os
import time

import numpy as np

import reference
import reference_sparse
import reference_wide
from jobs import train as train_job

THREADS = max(1, min(16, (os.cpu_count() or 2) - 1))
# the plan's fields the cell records and check compares
PLAN = ("feature_block", "feature_blocks", "compaction_ladder",
        "split_fusion")


def _surface() -> None:
    """An error where the library lacks what this job reads."""
    import lightgbm_tpu as lgb
    from lightgbm_tpu.models.gbdt import GBDT
    from lightgbm_tpu.ops import pallas_hist
    if (not hasattr(pallas_hist, "feature_block")
            or not hasattr(lgb.Booster, "hist_plan")
            or not hasattr(GBDT, "hist_feature_blocks")):
        raise RuntimeError(
            "this lightgbm_tpu has no pallas_hist.feature_block, no "
            "Booster.hist_plan or no GBDT.hist_feature_blocks: its "
            "histogram kernel is not blocked over features and it cannot "
            "report what the cell lists")


def setup(ctx) -> dict:
    import jax
    import lightgbm_tpu as lgb
    from lightgbm_tpu import binning
    _surface()
    cfg, params = ctx.cfg, train_job._params(ctx)
    rows, valid_rows = int(cfg["data"]["rows"]), int(cfg["data"]["valid_rows"])
    with ctx.phase("datagen"):
        X, y = ctx.data.make(cfg["data"], ctx.seed, rows + valid_rows, rows)
    st = {"X": X[:rows], "y": y[:rows], "Xv": X[rows:], "yv": y[rows:],
          "params": params, "parallel": False}
    norms = np.sqrt(np.einsum("ij,ij->i", st["X"][:4096], st["X"][:4096]))
    ctx.log(f"datagen: {ctx.phases['datagen']:.1f} s {X.dtype} {X.shape} "
            f"positives={float(st['y'].mean()):.4f} row norms "
            f"{float(norms.min()):.6f}..{float(norms.max()):.6f} column sd "
            f"{float(st['X'][:20000].std(axis=0).mean()):.6f}")

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
    st["binned_on_device"] = bool(ds.binned_on_device)
    ctx.log(f"construct: {ctx.phases['construct']:.1f} s rows={ds.num_data} "
            f"columns={ds.num_used_features()} of {X.shape[1]} "
            f"bins={ds.max_num_bins} (fewest a column "
            f"{min(m.num_bin for m in used)}) on_device_quantiser="
            f"{ds.binned_on_device} host_slice_equal={st['construct_ok']}")
    st["ds"] = ds

    with ctx.phase("tune"):
        plan = lgb.Booster(params=params, train_set=ds).hist_plan()
    st["plan"] = plan
    ctx.log(f"tune: {ctx.phases['tune']:.1f} s -> {plan}")
    return st


def window(ctx, st: dict, seconds: float, trace) -> dict:
    res = train_job.window(ctx, st, seconds, trace)
    gb = st["booster"]._boosting
    # feature blocks one kernel launch walks, scaled so that the counter
    # reader's division by the window's iterations gives it back
    ctx.counters["hist_feature_blocks"] = float(
        gb.hist_feature_blocks) * ctx.units
    return res


def _root(ctx, bad, name, tree, X, y, cnt, ysum, bounds, min_data, min_hess):
    """The root split of a first tree against the search over ALL columns:
    gain within the cell's tolerance, child counts exact."""
    t0 = time.time()
    n = len(y)
    gain, f_np, t_np, left_np = reference_wide.root_split(
        cnt, ysum, y, bounds, min_data, min_hess)
    f_sys, thr = int(tree["split_feature"][0]), float(tree["threshold"][0])
    gain_sys, left_raw = reference_wide.gain_of_raw_split(
        X[:, f_sys], y, thr, min_data, min_hess)
    left_sys = reference.child_count(tree, int(tree["left_child"][0]))
    right_sys = reference.child_count(tree, int(tree["right_child"][0]))
    short = (gain - gain_sys) / gain
    tol = float(ctx.cell["root_gain_rel_tolerance"])
    ctx.log(f"root_split[{name}]: numpy best column={f_np} bin<={t_np} "
            f"gain={gain:.6f} left={left_np} over {len(bounds)} columns; "
            f"system column={f_sys} x<={thr!r} gain(numpy)={gain_sys:.6f} "
            f"shortfall={short:.3e} (tolerance {tol}) counts system="
            f"{left_sys}/{right_sys} numpy={left_raw}/{n - left_raw} "
            f"({time.time() - t0:.1f} s)")
    if not short <= tol:
        bad.append(f"{name}: root split gain falls short of numpy's by "
                   f"{short:.3e}")
    if (left_sys, right_sys) != (left_raw, n - left_raw):
        bad.append(f"{name}: root child counts {left_sys}/{right_sys} "
                   f"differ from numpy's {left_raw}/{n - left_raw}")


def _leaves(ctx, bad, name, text, tree, X, y, params, tol=None):
    """Every leaf of a first tree against a traversal of the raw values:
    the counts exactly, and the printed values against float64 sums over
    the leaf's rows (the MEDIAN difference limited where ``tol`` is
    given; the largest is one leaf's, where a small child takes the
    rounding of a large parent's total)."""
    t0 = time.time()
    leaf = reference_wide.leaf_index(tree, X, THREADS)
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


def _probe(ctx, bad, st, X, binsT, bounds, B, min_data, min_hess):
    """One tree by the step the window timed, on the decision-list label
    over three columns of every feature block."""
    import jax
    import lightgbm_tpu as lgb
    from lightgbm_tpu import compile_cache
    ds, params, plan = st["ds"], st["params"], st["plan"]
    t0 = time.time()
    f = len(bounds)
    cols = reference_wide.designated_columns(f, plan["feature_block"] or f)
    top = [len(bounds[int(c)]) for c in cols]
    y2, steps = reference_wide.decision_list(binsT, cols, top, ctx.seed)
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
    found = reference_wide.planted_found(tree, cols, top, bounds)
    took = [r for _, r, _ in steps]
    ctx.log(f"probe: {len(cols)} designated columns in "
            f"{plan['feature_blocks']} feature blocks of "
            f"{plan['feature_block']} ({cols.tolist()}); a step takes "
            f"{min(took)}..{max(took)} rows, label 1 in "
            f"{float(y2.mean()):.4f}; one tree of {tree['num_leaves']} "
            f"leaves splits {sum(found)} of them at the planted bound, "
            f"{int(np.isin(tree['split_feature'], cols).sum())} of its "
            f"{tree['num_leaves'] - 1} splits on a designated column; fused "
            f"steps built for it: {built} ({time.time() - t0:.1f} s)")
    if built and jax.default_backend() == "tpu":
        bad.append("the probe tree was grown by another step than the one "
                   "the window timed")
    missing = [int(c) for c, ok in zip(cols, found) if not ok]
    if missing:
        bad.append(f"the probe tree does not split columns {missing} at "
                   f"the planted bound")
    cnt2, ysum2 = reference_wide.column_histograms(binsT, y2, B, THREADS)
    _root(ctx, bad, "probe", tree, X, y2, cnt2, ysum2, bounds, min_data,
          min_hess)
    _leaves(ctx, bad, "probe", text, tree, X, y2, params)


def check(ctx, st: dict) -> list:
    """Reasons why the run is not correct (empty: correct)."""
    import jax
    from lightgbm_tpu.config import Config
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
    bounds = reference_wide.bounds_of(ds.mappers)
    binsT, cnt, ysum = reference_wide.whole_histograms(X, y, bounds, B,
                                                       THREADS)
    ctx.log(f"reference: bins and histograms of {X.shape} raw values "
            f"({time.time() - t0:.1f} s)")
    text = booster.model_to_string(num_iteration=1)
    tree = reference.parse_model(text)[0]
    _root(ctx, bad, "tree 0", tree, X, y, cnt, ysum, bounds, min_data,
          min_hess)
    _leaves(ctx, bad, "tree 0", text, tree, X, y, params,
            float(ctx.cell["leaf_value_median_abs_tolerance"]))
    fb = st["plan"]["feature_block"] or len(bounds)
    blocks_split = sorted({int(j) // fb for j in tree["split_feature"]})
    ctx.log(f"tree 0 splits on {len(set(tree['split_feature'].tolist()))} "
            f"columns of {len(blocks_split)} feature blocks "
            f"{blocks_split}")
    # (d) the probe, which holds every feature block
    _probe(ctx, bad, st, X, binsT, bounds, B, min_data, min_hess)
    del binsT

    # (e) quality anchor
    if not st["auc"] >= float(ctx.cell["auc_anchor"]):
        bad.append(f"valid AUC {st['auc']:.6f} below the anchor")
    # (f) nothing compiled inside the window; the kernel is in the step;
    # the plan is the one the cell records
    if st["compiles_in_window"]:
        bad.append(f"{st['compiles_in_window']} compile requests inside "
                   f"the window")
    hm = gb._hist_method()
    kernels = train_job._kernels_in_program(gb, hm)
    ctx.log(f"kernels_in_program={sorted(set(kernels))} x{len(kernels)} "
            f"method={hm}")
    if not hm.startswith("pallas"):
        bad.append(f"executed histogram method {hm!r} is not a Pallas kernel")
    if on_chip and not any(k.startswith("hist_tiles") for k in kernels):
        bad.append("no compiled hist_tiles kernel in the fused step")
    if not on_chip and not gb._hist_interpret():
        bad.append("off the chip the kernels must run interpreted")
    took = {k: st["plan"][k] for k in PLAN}
    if booster.hist_plan() != st["plan"]:
        bad.append(f"the plan moved during the run: {st['plan']} before, "
                   f"{booster.hist_plan()} after")
    want = ctx.cell.get("plan")
    if on_chip and took != want:
        bad.append(f"the run took the plan {took}, the cell records {want}")
    ctx.log(f"check: {time.time() - t_check:.1f} s after the window")
    return bad

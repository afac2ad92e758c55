"""Job ``sparse_train``: lgb.Dataset over a scipy CSR matrix -> (tune) ->
lgb.train, timed per iteration.

Job ``train``'s loop, window and stamps (it calls them: a sparse ``X`` goes
through ``lgb.train`` and ``Booster.predict`` like a dense one), for a job
whose storage and search the library chooses from the data: exclusive
feature bundles, (row, bin) streams for the columns that are mostly one
bin, and the search that handles both. What differs from ``train``: the
plain reference is ``reference_sparse.py``, over the RAW columns, and
``check`` holds the first tree to it at full size, root split, every leaf
count and every leaf value; a PROBE tree, grown by the step the window
timed on a label that only the stream columns' members explain, is held to
the same reference, so the streams' planes and their per-split routing are
compared where they decide the answer; the storage the run took is compared
with what the cell's file records; the construct's stages and counts are
read from the library's own surface, ``Dataset.construct_stats``.

The library finds its bin bounds and its bundles on a sample of rows that
it draws by POSITION. The rows at those positions are the same on every
``--seed`` (``data/expo.py``'s ``keep``): the storage the library chooses
is then the data set's, not the row order's, and every seed runs one
compiled step (PERF.md, Findings, PR 33: left to the order, the stream
width and the step's time with it moved by 10% over six seeds).

Before any device work ``setup`` asks the library for what this cell
reads: a library without it cannot report what the cell lists, and the run
ends there, within seconds, instead of training for minutes.
"""

import time

import numpy as np

import reference
import reference_sparse
from jobs import train as train_job

# what check and the layer metrics read of Dataset.construct_stats
STATS = ("efb_used_features", "efb_columns", "efb_bundle_bins",
         "efb_conflict_rows", "sparse_stream_columns",
         "sparse_stream_entries", "sparse_stream_slots", "efb_fit_mappers_s",
         "efb_find_bundles_s", "efb_place_s", "sparse_extract_s")
# the probe's label: its share of ones in the rows that hold no chosen
# member of a stream column, and how often a member is chosen
PROBE_NOISE, PROBE_EVERY = 0.25, 2


def _surface() -> None:
    """An error where the library lacks the scopes and the decoder this
    job reads."""
    import lightgbm_tpu as lgb
    from lightgbm_tpu.utils import profiling
    if (not {"sparse_hist", "sparse_route"} <= set(profiling.SCOPES)
            or not hasattr(lgb.Dataset, "unbundled_bins")):
        raise RuntimeError(
            "this lightgbm_tpu has no sparse_hist / sparse_route scope or "
            "no Dataset.unbundled_bins: it cannot report what the cell "
            "lists")


def _conflict_rows(ds, host: np.ndarray) -> np.ndarray:
    """Rows of unbundled host bins ``[k, F_used]`` in which two members of
    one bundle are both off their most-frequent bin."""
    mode = np.array([ds.mappers[int(j)].most_freq_bin
                     for j in ds.used_features])
    off = host != mode[None, :]
    bad = np.zeros(len(host), dtype=bool)
    for bd in ds.bundles:
        if len(bd.members) > 1:
            bad |= off[:, bd.members].sum(axis=1) > 1
    return bad


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
        del X
    ctx.log(f"datagen: {ctx.phases['datagen']:.1f} s {st['X'].format} "
            f"{st['X'].dtype} {st['X'].shape} nnz={st['X'].nnz} "
            f"({st['X'].nnz / rows:.2f} a row) positives="
            f"{float(st['y'].mean()):.4f}")

    with ctx.phase("construct"):
        ds = lgb.Dataset(st["X"], label=st["y"], params=params)
        ds.construct()
        jax.block_until_ready(ds.bins)
    stats = dict(ds.construct_stats or {})
    missing = [k for k in STATS if k not in stats]
    if missing:
        raise RuntimeError(f"Dataset.construct_stats lacks {missing}")
    st["stats"] = stats
    # the stages' seconds are phases, the counts counters, for the readers
    for k, v in stats.items():
        if k.endswith("_s"):
            ctx.phases[k[:-2]] = float(v)
        else:
            ctx.counters[k] = float(v)

    # the device storage decoded back to one bin a used feature, against
    # the host quantiser on the raw slice; a row in which two members of a
    # bundle collide keeps the later member only (EFB's approximation)
    k = min(rows, 4096)
    used = [ds.mappers[int(j)] for j in ds.used_features]
    host = binning.bin_data(
        st["X"][:k].toarray()[:, ds.used_features].astype(np.float64), used)
    differ = (ds.unbundled_bins(0, k) != host).any(axis=1)
    st["construct_ok"] = not bool((differ & ~_conflict_rows(ds, host)).any())
    ctx.log(f"construct: {ctx.phases['construct']:.1f} s rows={ds.num_data} "
            f"device_columns={ds.num_used_features()} dense="
            f"{ds.bins.shape[1]} bins={ds.max_num_bins} stats={stats} "
            f"bundles(members, bins)="
            f"{[(len(b.members), b.num_bin) for b in ds.bundles]} "
            f"stream_columns={None if ds.sp_cols is None else list(ds.sp_cols)}"
            f"; decoded slice of {k} rows: {int(differ.sum())} rows differ "
            f"from the host quantiser, all of them conflict rows="
            f"{st['construct_ok']}")
    st["ds"] = ds

    with ctx.phase("tune"):
        gb = lgb.Booster(params=params, train_set=ds)._boosting
        hm = gb._hist_method()
        statics = gb._serial_grow_statics(hm)
    st["split_fusion"] = bool(statics["split_fusion"])
    ctx.log(f"tune: {ctx.phases['tune']:.1f} s -> {hm} "
            f"block={statics['hist_block']} "
            f"tile_leaves={statics['tile_leaves']} "
            f"split_fusion={statics['split_fusion']} "
            f"compaction_ladder={list(statics['compaction_ladder'])} "
            f"sp_cols={list(statics['sp_cols'])}")
    return st


def window(ctx, st: dict, seconds: float, trace) -> dict:
    res = train_job.window(ctx, st, seconds, trace)
    # the kernel streams the DENSE device columns only: the stream
    # columns' planes are an XLA scatter-add (scope sparse_hist)
    ctx.work["features"] = int(st["ds"].bins.shape[1])
    return res


def _stream_members(ds) -> list:
    """The original columns stored in each (row, bin) stream column."""
    if not ds.has_sparse_cols:
        return []
    return [np.array(sorted(int(ds.used_features[m])
                            for m in ds.bundles[int(c)].members))
            for c in ds.sp_cols]


def _probe_label(members: list, X_csc, seed: int) -> np.ndarray:
    """A label that only members of the stream columns explain, drawn from
    the seed: 1 in a PROBE_NOISE share of the rows, and in a larger share
    q of the rows where one of every PROBE_EVERY-th member of a stream
    column is non-zero. q falls with the member's rows k as
    sqrt(rarest k / k), from 1 at the rarest: a split's gain grows with
    k (q - mean)^2, so every chosen member offers about the same gain, far
    above the noise's, and the members of all stream columns are cut out
    side by side (the grower splits every leaf a round, and a common
    member's gain would else keep the rare columns waiting past the leaf
    budget). Neighbours in a bundle differ, so the members come out one by
    one: every such split is searched on the streams' planes and routed
    through the stream's side of ``_apply_split``."""
    n = X_csc.shape[0]
    chosen = [j for cols in members for j in cols[::PROBE_EVERY]]
    rows = np.diff(X_csc.indptr)[chosen]
    q = np.full(n, PROBE_NOISE)
    for j, k in sorted(zip(chosen, rows), key=lambda jk: -jk[1]):
        at = X_csc.indices[X_csc.indptr[j]:X_csc.indptr[j + 1]]
        q[at] = PROBE_NOISE + (1 - PROBE_NOISE) * np.sqrt(rows.min() / k)
    return (np.random.default_rng([seed, 0x9E37]).random(n) < q).astype(
        np.float32)


def _root_against_raw(ctx, bad, name, tree, X, y, bounds, params, conflicts):
    """The root split of a first tree against numpy over the raw columns,
    at full size: gain within the cell's tolerance, child counts exact up
    to the conflict rows. Returns numpy's best column."""
    t0 = time.time()
    n = X.shape[0]
    min_data = float(params["min_data_in_leaf"])
    min_hess = float(params["min_sum_hessian_in_leaf"])
    gain, f_np, t_np, left_np = reference_sparse.root_split(
        X, y, bounds, min_data, min_hess)
    f_sys = int(tree["split_feature"][0])
    thr = float(tree["threshold"][0])
    gain_sys, left_raw = reference_sparse.gain_of_raw_split(
        X, f_sys, y, thr, min_data, min_hess)
    left_sys = reference.child_count(tree, int(tree["left_child"][0]))
    right_sys = reference.child_count(tree, int(tree["right_child"][0]))
    short = (gain - gain_sys) / gain
    tol = float(ctx.cell["root_gain_rel_tolerance"])
    ctx.log(f"root_split[{name}]: numpy best column={f_np} x<={t_np!r} "
            f"gain={gain:.6f} left={left_np}; system column={f_sys} "
            f"x<={thr!r} gain(numpy)={gain_sys:.6f} shortfall={short:.3e} "
            f"(tolerance {tol}) counts system={left_sys}/{right_sys} "
            f"numpy={left_raw}/{n - left_raw} ({time.time() - t0:.1f} s)")
    if not short <= tol:
        bad.append(f"{name}: root split gain falls short of numpy's by "
                   f"{short:.3e}")
    if abs(left_sys - left_raw) > conflicts or left_sys + right_sys != n:
        bad.append(f"{name}: root child counts differ from numpy's by more "
                   f"than the conflict rows")
    return f_np


def _leaves_against_raw(ctx, bad, name, text, tree, X, y, params, conflicts,
                        tol=None):
    """Every leaf of a first tree against a traversal of the raw values,
    at full size: the counts (off by at most the conflict rows) and, in
    the leaves whose count agrees, the printed value against float64 sums
    of the leaf's rows. ``tol`` limits the MEDIAN of those differences
    (the largest is logged: it is one leaf's, where a small child takes
    the rounding of a large parent's total); without it the values are
    logged only."""
    t0 = time.time()
    leaf = reference_sparse.leaf_index(tree, X)
    ref_counts = reference_sparse.leaf_counts(tree, X, leaf)
    off = int(np.abs(ref_counts - tree["leaf_count"]).sum())
    same = ref_counts == tree["leaf_count"]
    ref_values = reference_sparse.leaf_values(
        tree, leaf, y, float(reference_sparse.tree_field(text, 0,
                                                          "shrinkage")[0]),
        float(params.get("lambda_l2", 0.0)))
    err = np.where(same, np.abs(tree["leaf_value"] - ref_values), -1.0)
    far = []
    for k in np.argsort(-err)[:3]:
        node, side = next(
            (i, s) for i in range(tree["num_leaves"] - 1)
            for s, c in (("left", tree["left_child"][i]),
                         ("right", tree["right_child"][i])) if c == ~k)
        far.append(f"leaf {k}: {int(ref_counts[k])} rows, {side} of "
                   f"column {int(tree['split_feature'][node])} at "
                   f"{float(tree['threshold'][node])!r} in "
                   f"{int(tree['internal_count'][node])} rows, value "
                   f"{tree['leaf_value'][k]:.9f} against "
                   f"{ref_values[k]:.9f}")
    err = err[same]
    worst, mid = (float(err.max()), float(np.median(err))) if same.any() \
        else (float("inf"), float("inf"))
    ctx.log(f"leaves[{name}]: {tree['num_leaves']} leaves, "
            f"{int(tree['leaf_count'].sum())} rows; sum |model - raw "
            f"traversal| = {off} over {int((~same).sum())} leaves (a "
            f"misrouted row counts twice; efb_conflict_rows={conflicts}); "
            f"leaf values against float64 sums over the {int(same.sum())} "
            f"leaves that agree: median |difference| {mid:.3e} (tolerance "
            f"{tol}), largest {worst:.3e}; farthest: {'; '.join(far)} "
            f"({time.time() - t0:.1f} s)")
    if off > 2 * conflicts:
        bad.append(f"{name}: leaf counts differ from the raw traversal's by "
                   f"{off / 2:.0f} rows, more than the {conflicts} conflict "
                   f"rows")
    if 2 * int(same.sum()) < tree["num_leaves"]:
        bad.append(f"{name}: fewer than half of the leaf counts agree with "
                   f"the raw traversal")
    if tol is not None and not mid <= tol:
        bad.append(f"{name}: the leaf values are {mid:.3e} off the float64 "
                   f"sums over their rows, by the median")


def _probe(ctx, bad, st, X, bounds, conflicts):
    """One tree by the step the window timed, on the probe label."""
    import jax
    import lightgbm_tpu as lgb
    from lightgbm_tpu import compile_cache
    ds, params = st["ds"], st["params"]
    members = _stream_members(ds)
    if not members:
        ctx.log("probe: the storage has no stream column, nothing to probe")
        return
    t0 = time.time()
    y2 = _probe_label(members, X, ctx.seed)
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
    owner = {int(j): i for i, cols in enumerate(members) for j in cols}
    hits = np.bincount([owner[int(j)] for j in tree["split_feature"]
                        if int(j) in owner], minlength=len(members))
    ctx.log(f"probe: label 1 in {float(y2.mean()):.4f} of the rows "
            f"({sum(len(c[::PROBE_EVERY]) for c in members)} of "
            f"{sum(len(c) for c in members)} stream members, the rest at "
            f"{PROBE_NOISE}); one tree of {tree['num_leaves']} leaves, "
            f"its splits on a member of each stream column "
            f"{ {int(c): int(k) for c, k in zip(ds.sp_cols, hits)} }, fused "
            f"steps built for it: {built} ({time.time() - t0:.1f} s)")
    if built and jax.default_backend() == "tpu":
        bad.append("the probe tree was grown by another step than the one "
                   "the window timed")
    if not (hits > 0).all():
        bad.append("the probe tree does not split on every stream column")
    f_np = _root_against_raw(ctx, bad, "probe", tree, X, y2, bounds, params,
                             conflicts)
    if f_np not in owner:
        bad.append("the probe's best root split is not on a stream member")
    _leaves_against_raw(ctx, bad, "probe", text, tree, X, y2, params,
                        conflicts)


def check(ctx, st: dict) -> list:
    """Reasons why the run is not correct (empty: correct)."""
    import jax
    bad = list(st["degraded"])
    booster, ds, params, stats = st["booster"], st["ds"], st["params"], \
        st["stats"]
    gb = booster._boosting
    on_chip = jax.default_backend() == "tpu"
    conflicts = int(stats["efb_conflict_rows"])
    if not st["construct_ok"]:
        bad.append("the decoded device storage differs from the host "
                   "quantiser outside conflict rows")

    # (a), (b) tree 0 against numpy over the raw columns, at full size: the
    # root split, every leaf's count and the leaves' values
    X = st["X"].tocsc()
    bounds = [None if m.is_trivial else m.bin_upper_bound
              for m in ds.mappers]
    text = booster.model_to_string(num_iteration=1)
    tree = reference.parse_model(text)[0]
    _root_against_raw(ctx, bad, "tree 0", tree, X, st["y"], bounds, params,
                      conflicts)
    _leaves_against_raw(ctx, bad, "tree 0", text, tree, X, st["y"], params,
                        conflicts,
                        float(ctx.cell["leaf_value_median_abs_tolerance"]))
    streamed = np.concatenate(_stream_members(ds) or [np.zeros(0, np.int64)])
    uses = [int(np.isin(t["split_feature"], streamed).sum())
            for t in reference.parse_model(booster.model_to_string())]
    ctx.log(f"stream_splits: splits on a stream column's member, by tree: "
            f"{uses}")
    # (g) the stream columns where they decide the answer: a probe tree
    _probe(ctx, bad, st, X, bounds, conflicts)

    # (c) quality anchor
    if not st["auc"] >= float(ctx.cell["auc_anchor"]):
        bad.append(f"valid AUC {st['auc']:.6f} below the anchor")
    # (d) nothing compiled inside the window; the kernel is in the step
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
    # (f) the storage and the search are the ones the cell records
    took = {"efb_columns": int(stats["efb_columns"]),
            "sparse_stream_columns": int(stats["sparse_stream_columns"]),
            "split_fusion": st["split_fusion"]}
    want = ctx.cell.get("storage")
    if on_chip and took != want:
        bad.append(f"the run took {took}, the cell records {want}")
    return bad

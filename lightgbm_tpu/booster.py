"""Booster: the user-facing trained-model handle.

Mirrors the reference Python ``Booster`` (reference:
python-package/lightgbm/basic.py Booster) over the boosting layer, playing
the role of the C API's Booster wrapper (reference: src/c_api.cpp:52-106) —
here there is no C boundary; the boosting object is held directly.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional

import numpy as np

from .basic import Dataset
from .config import Config
from .models.boosting import create_boosting
from .utils import log


class Booster:
    def __init__(self, params: Optional[Dict[str, Any]] = None,
                 train_set: Optional[Dataset] = None,
                 model_file: Optional[str] = None,
                 model_str: Optional[str] = None):
        self.params = dict(params or {})
        self.config = Config.from_params(self.params)
        self.best_iteration = -1
        self.best_score: Dict[str, Dict[str, float]] = {}
        self._train_set = train_set
        if model_file is not None or model_str is not None:
            from .io.model_text import load_model
            if model_file is not None:
                with open(model_file) as fh:
                    model_str = fh.read()
            self._boosting = load_model(model_str, self.config)
        elif train_set is not None:
            if not isinstance(train_set, Dataset):
                raise TypeError("Training data should be Dataset instance")
            # num_machines > 1: bootstrap jax.distributed before any device
            # work (the reference calls Network::Init before training,
            # application.cpp:167-178)
            from . import distributed
            distributed.maybe_init_from_config(self.config)
            # merge dataset params before construction
            merged = dict(train_set.params or {})
            merged.update(self.params)
            train_set.params = merged
            self._boosting = create_boosting(self.config, train_set)
            # params identity BEFORE any mid-training reset_parameter
            # mutation: both the checkpointing and the resuming run hash
            # their construction-time config, so learning-rate schedules
            # don't produce spurious resume mismatches
            from .checkpoint import params_hash
            self._initial_params_hash = params_hash(self.config)
        else:
            raise ValueError("need at least one of train_set, model_file or model_str")

    # ------------------------------------------------------------ training
    def add_valid(self, data: Dataset, name: str) -> "Booster":
        self._boosting.add_valid(data, name)
        return self

    def update(self, train_set: Optional[Dataset] = None, fobj=None) -> bool:
        """One boosting iteration; with ``fobj`` the gradients come from
        Python (reference: basic.py Booster.update + c_api.cpp:1645
        LGBM_BoosterUpdateOneIterCustom)."""
        if train_set is not None and train_set is not self._train_set:
            log.fatal("Replacing the training set in update() is not supported")
        if fobj is None:
            return self._boosting.train_one_iter()
        grad, hess = fobj(np.asarray(self._boosting.train_score, dtype=np.float64),
                          self._train_set)
        return self._boosting.train_one_iter(grad, hess)

    def rollback_one_iter(self) -> "Booster":
        self._boosting.rollback_one_iter()
        return self

    def current_iteration(self) -> int:
        return self._boosting.current_iteration()

    def num_trees(self) -> int:
        return self._boosting.num_trees

    def num_model_per_iteration(self) -> int:
        return self._boosting.num_tree_per_iteration

    def hist_plan(self) -> Dict[str, Any]:
        """How this booster builds its histograms on the training set it
        holds: method, leaves a pass, rows and device columns a kernel
        body, feature blocks a launch, fused split search, compaction
        rungs (models/gbdt.py GBDT.hist_plan). Needs the training set."""
        return self._boosting.hist_plan()

    def reset_parameter(self, params: Dict[str, Any]) -> "Booster":
        """reference: basic.py Booster.reset_parameter (learning_rate etc.)."""
        self.params.update(params)
        self.config = Config.from_params(self.params)
        self._boosting.reset_config(self.config)
        return self

    # ---------------------------------------------------------------- eval
    def eval_set(self, feval=None):
        return self._boosting.eval_set(feval)

    def eval(self, data, name: str, feval=None):
        """Evaluate the configured metrics on an arbitrary train-aligned
        Dataset (reference: basic.py Booster.eval / GBDT valid metric
        flow). Returns (name, metric, value, bigger_is_better) tuples."""
        import numpy as np
        b = self._boosting
        score = np.asarray(b.score_dataset(data), dtype=np.float64)
        return b.eval_metrics(score, data, name, feval)

    def eval_train(self, feval=None):
        old = self.config.is_provide_training_metric
        self.config.is_provide_training_metric = True
        try:
            return [r for r in self._boosting.eval_set(feval) if r[0] == "training"]
        finally:
            self.config.is_provide_training_metric = old

    def eval_valid(self, feval=None):
        return [r for r in self._boosting.eval_set(feval) if r[0] != "training"]

    # ------------------------------------------------------------- predict
    def predict(self, data, start_iteration: int = 0,
                num_iteration: Optional[int] = None, raw_score: bool = False,
                pred_leaf: bool = False, pred_contrib: bool = False,
                pred_early_stop: bool = False,
                pred_early_stop_freq: int = 10,
                pred_early_stop_margin: float = 10.0,
                **kwargs) -> np.ndarray:
        """Predict on new data (reference: basic.py Booster.predict).

        Serving runs on the device-resident inference engine
        (models/predict_engine.py): one ensemble-scan dispatch with f64
        accumulation on device, returning only the [N, K] result —
        batch shapes are bucketed so varying sizes reuse compiled
        programs. Tuned by the ``predict_bucket_min_rows`` /
        ``predict_chunk_rows`` (streaming) / ``predict_sharded``
        (multi-device row sharding) / ``predict_accum`` params."""
        if num_iteration is None:
            num_iteration = self.best_iteration if self.best_iteration > 0 else -1
        if pred_leaf:
            return self._boosting.predict_leaf(data, num_iteration)
        if pred_contrib:
            return self._boosting.predict_contrib(data, num_iteration)
        return self._boosting.predict(data, raw_score=raw_score,
                                      num_iteration=num_iteration,
                                      start_iteration=start_iteration,
                                      pred_early_stop=pred_early_stop,
                                      pred_early_stop_freq=pred_early_stop_freq,
                                      pred_early_stop_margin=pred_early_stop_margin)

    # ------------------------------------------------------------ model IO
    def save_model(self, filename: str, num_iteration: Optional[int] = None,
                   start_iteration: int = 0) -> "Booster":
        # atomic (tmp + fsync + rename): a crash mid-write must leave the
        # previous file, never a truncated model.txt that parses into a
        # silently shorter model
        from .utils.atomic_write import atomic_write_text
        atomic_write_text(filename,
                          self.model_to_string(num_iteration, start_iteration))
        return self

    def model_to_string(self, num_iteration: Optional[int] = None,
                        start_iteration: int = 0) -> str:
        from .io.model_text import dump_model_text
        if num_iteration is None:
            num_iteration = self.best_iteration if self.best_iteration > 0 else -1
        return dump_model_text(self._boosting, num_iteration, start_iteration)

    def dump_model(self, num_iteration: Optional[int] = None,
                   start_iteration: int = 0) -> dict:
        from .io.model_text import dump_model_json
        return dump_model_json(self._boosting, num_iteration or -1, start_iteration)

    # ------------------------------------------------------ importance etc
    def feature_importance(self, importance_type: str = "split",
                           iteration: Optional[int] = None) -> np.ndarray:
        """reference: gbdt.cpp FeatureImportance (split counts / total gains)."""
        imp = self._boosting.feature_importance(importance_type)
        if importance_type == "split":
            return imp.astype(np.int32)
        return imp

    def feature_name(self) -> List[str]:
        b = self._boosting
        ts = getattr(b, "train_set", None)
        if ts is not None:
            return ts.get_feature_names()
        return list(b.feature_names)

    def num_feature(self) -> int:
        b = self._boosting
        ts = getattr(b, "train_set", None)
        if ts is not None:
            return ts.num_total_features
        return b.max_feature_idx + 1

    # ----------------------------------------------- misc reference API
    def attr(self, key: str):
        """Runtime attribute (reference: basic.py Booster.attr/set_attr —
        a key/value store on the booster object)."""
        return getattr(self, "_attr", {}).get(key)

    def set_attr(self, **kwargs) -> "Booster":
        store = getattr(self, "_attr", None)
        if store is None:
            store = self._attr = {}
        for k, v in kwargs.items():
            if v is None:
                store.pop(k, None)
            else:
                store[k] = str(v)
        return self

    def set_train_data_name(self, name: str) -> "Booster":
        """reference: basic.py Booster.set_train_data_name."""
        self._train_data_name = name
        return self

    def free_dataset(self) -> "Booster":
        """Release the training data (reference: Booster.free_dataset —
        prediction and model IO keep working; further training does not).
        The binning metadata (mappers, bundles, missing routing) stays so
        new data can still be binned for prediction; the O(N) arrays go."""
        b = self._boosting
        b._flush_pending()
        ts = getattr(b, "train_set", None)
        if ts is not None:
            ts.bins = None
            ts._bins_T = None
            # the sparse-storage fields go together: leaving sp_cols set
            # would keep has_sparse_cols reporting True on a dataset whose
            # streams are gone (ADVICE r5 low)
            ts.drop_streams()
            ts._traversal_bins_cache = None
            ts.label = ts.weight = ts.init_score = None
            ts.raw_data_np = None
            # streaming-construct datasets must not keep the chunk source
            # pinned either (it may hold file handles or closures over
            # generator state) — the construct-re-entry audit twin of the
            # monolithic raw release above
            ts._chunk_source = None
        b.train_score = None
        # valid sets hold the other O(N) device arrays (bins, per-row
        # scores, raw caches) — the reference frees its datasets wholesale
        for vs in b.valid_sets:
            vs.bins = None
            vs._bins_T = None
            vs.raw_data_np = None
        b.valid_sets = []
        b.valid_names = []
        b._valid_scores = []
        b._valid_raw_cache = {}
        self._train_set = None
        return self

    def free_network(self) -> "Booster":
        """reference: Booster.free_network (tears down the comm layer)."""
        from . import distributed
        distributed.shutdown()
        return self

    def set_network(self, machines, local_listen_port: int = 12400,
                    listen_time_out: int = 120,
                    num_machines: int = 1) -> "Booster":
        """reference: Booster.set_network -> Network::Init; here the
        machine list feeds jax.distributed via distributed.init."""
        from . import distributed
        if isinstance(machines, (list, tuple)):
            machines = ",".join(str(m) for m in machines)
        distributed.init(machines=machines, num_machines=num_machines or None)
        return self

    def get_leaf_output(self, tree_id: int, leaf_id: int) -> float:
        """reference: Booster.get_leaf_output (Tree::LeafOutput)."""
        ht = self._boosting.host_trees[tree_id]
        return float(ht.leaf_value[leaf_id])

    def lower_bound(self) -> float:
        """Minimum possible raw score (reference: Booster.lower_bound ->
        GBDT sum of per-tree minima, tree.cpp:316 per-tree bounds)."""
        import numpy as np
        return float(sum(float(np.min(ht.leaf_value))
                         for ht in self._boosting.host_trees))

    def upper_bound(self) -> float:
        """Maximum possible raw score (reference: Booster.upper_bound)."""
        import numpy as np
        return float(sum(float(np.max(ht.leaf_value))
                         for ht in self._boosting.host_trees))

    def shuffle_models(self, start_iteration: int = 0,
                       end_iteration: int = -1) -> "Booster":
        """Randomly permute tree order in [start, end) iterations
        (reference: Booster.shuffle_models -> GBDT::ShuffleModels; the
        prediction SUM is order-independent, refit/early-stop sequences
        are not). Deterministic like the reference's fixed-seed
        ``Random tmp_rand(17)`` (gbdt.h:95): fresh boosters produce the
        same order, and like the reference's MEMBER rng, successive calls
        on one booster draw successive permutations rather than repeating
        the first."""
        import random
        b = self._boosting
        b._flush_pending()
        if not hasattr(b, "_shuffle_rand"):
            b._shuffle_rand = random.Random(17)
        k = b.num_tree_per_iteration
        total = len(b.trees) // k
        end = total if end_iteration <= 0 else min(end_iteration, total)
        idx = list(range(start_iteration, end))
        perm = idx[:]
        b._shuffle_rand.shuffle(perm)
        for attr in ("trees", "_host_trees", "tree_bias"):
            arr = getattr(b, attr)
            orig = list(arr)
            for src, dst in zip(idx, perm):
                for c in range(k):
                    arr[dst * k + c] = orig[src * k + c]
        b._mt_cache.clear()
        b._stacked_cache = None
        b._engine_cache.clear()   # stacked order changed under the engine
        b._contrib_tree_cache = None
        return self

    def get_split_value_histogram(self, feature, bins=None):
        """Histogram of a feature's split thresholds across the model
        (reference: Booster.get_split_value_histogram). Returns
        (counts, bin_edges) like np.histogram."""
        import numpy as np
        model = self.dump_model()
        feature_names = model["feature_names"]
        feat_idx = feature_names.index(feature) if isinstance(feature, str) \
            else int(feature)
        values = []

        def walk(node):
            if "split_feature" in node:
                if node["split_feature"] == feat_idx \
                        and node["decision_type"] == "<=":
                    values.append(float(node["threshold"]))
                walk(node["left_child"])
                walk(node["right_child"])

        for ti in model["tree_info"]:
            walk(ti["tree_structure"])
        if not values:
            raise ValueError("feature was never used for splitting")
        return np.histogram(values,
                            bins=bins or max(10, len(set(values))))

    def trees_to_dataframe(self):
        """All nodes of all trees as one pandas DataFrame (reference:
        basic.py Booster.trees_to_dataframe — same column names)."""
        import pandas as pd
        model = self.dump_model()
        feature_names = model["feature_names"]
        rows = []

        def walk(tree_index, node, depth, parent):
            # a splitless tree's dump is a bare {'leaf_value': ...} with no
            # leaf_index (io/model_text.py single-leaf form)
            node_idx = (f"{tree_index}-S{node['split_index']}"
                        if "split_index" in node
                        else f"{tree_index}-L{node.get('leaf_index', 0)}")
            if "split_feature" in node:
                rows.append({
                    "tree_index": tree_index, "node_depth": depth,
                    "node_index": node_idx,
                    "left_child": None, "right_child": None,
                    "parent_index": parent,
                    "split_feature": feature_names[node["split_feature"]],
                    "split_gain": node.get("split_gain"),
                    "threshold": node.get("threshold"),
                    "decision_type": node.get("decision_type"),
                    "missing_direction":
                        "left" if node.get("default_left") else "right",
                    "missing_type": node.get("missing_type"),
                    "value": node.get("internal_value"),
                    "weight": node.get("internal_weight"),
                    "count": node.get("internal_count")})
                me = len(rows) - 1
                lid = walk(tree_index, node["left_child"], depth + 1,
                           node_idx)
                rid = walk(tree_index, node["right_child"], depth + 1,
                           node_idx)
                rows[me]["left_child"] = lid
                rows[me]["right_child"] = rid
            else:
                rows.append({
                    "tree_index": tree_index, "node_depth": depth,
                    "node_index": node_idx,
                    "left_child": None, "right_child": None,
                    "parent_index": parent,
                    "split_feature": None, "split_gain": None,
                    "threshold": None, "decision_type": None,
                    "missing_direction": None, "missing_type": None,
                    "value": node.get("leaf_value"),
                    "weight": node.get("leaf_weight"),
                    "count": node.get("leaf_count")})
            return node_idx

        for ti in model["tree_info"]:
            walk(ti["tree_index"], ti["tree_structure"], 1, None)
        return pd.DataFrame(rows)

    def model_from_string(self, model_str: str) -> "Booster":
        """Replace this booster's model with one parsed from text
        (reference: basic.py Booster.model_from_string)."""
        from .io.model_text import load_model
        self._boosting = load_model(model_str, self.config)
        return self

    def refit(self, data, label=None, weight=None, group=None,
              decay_rate: float = 0.9) -> "Booster":
        """Re-fit the leaf values of the existing tree structure on new data
        (reference: GBDT::RefitTree gbdt.cpp:285-321 +
        SerialTreeLearner::FitByExistingTree serial_tree_learner.cpp:211-244;
        Python surface basic.py Booster.refit). Returns a NEW Booster.
        Linear-leaf coefficients are kept as-is; only leaf constants refit."""
        from .io.model_text import load_model
        from .objectives import create_objective
        import jax.numpy as jnp

        loaded = load_model(self.model_to_string(), Config.from_params(self.params))
        if label is None and hasattr(data, "get_label"):
            label = data.get_label()
            weight = data.get_weight() if weight is None else weight
            group = data.get_group() if group is None else group
            data = data.data
        X = data
        label = np.asarray(label, dtype=np.float64).reshape(-1)
        leaf = loaded.predict_leaf(X)               # [N, T]
        n = leaf.shape[0]
        cfg = loaded.config
        objective = create_objective(cfg)
        if objective is None:
            log.fatal("Cannot refit a model without a built-in objective")
        objective.init(label, None if weight is None else
                       np.asarray(weight, np.float64).reshape(-1),
                       None if group is None else
                       np.asarray(group, np.int64).reshape(-1))
        k = loaded.num_tree_per_iteration
        score = np.zeros((n, k) if k > 1 else (n,), np.float64)
        l1, l2 = cfg.lambda_l1, cfg.lambda_l2
        mds = cfg.max_delta_step
        eps = 1e-15

        def leaf_output(sg, sh):
            out = -np.sign(sg) * np.maximum(np.abs(sg) - l1, 0.0) / (sh + l2)
            if mds > 0:
                out = np.clip(out, -mds, mds)
            return out

        Xmat = None
        if any(t.is_linear for t in loaded.trees):
            Xmat = np.asarray(X, np.float64)
            if Xmat.ndim == 1:
                Xmat = Xmat.reshape(1, -1)
        iters = loaded.num_iteration
        for it in range(iters):
            g, h = objective.get_grad_hess(jnp.asarray(score, jnp.float32))
            g = np.asarray(g, np.float64)
            h = np.asarray(h, np.float64)
            for c in range(k):
                tree = loaded.trees[it * k + c]
                lp = leaf[:, it * k + c]
                gc = g[:, c] if k > 1 else g
                hc = h[:, c] if k > 1 else h
                nl = tree.num_leaves
                sum_g = np.bincount(lp, weights=gc, minlength=nl)[:nl]
                sum_h = np.bincount(lp, weights=hc, minlength=nl)[:nl] + eps
                new_out = leaf_output(sum_g, sum_h) * tree.shrinkage
                tree.leaf_value = (decay_rate * tree.leaf_value
                                   + (1.0 - decay_rate) * new_out)
                if tree.is_linear:
                    # re-solve the per-leaf ridge system and decay-blend
                    # const/coeffs (linear_tree_learner.cpp:320-380
                    # CalculateLinear(is_refit=true))
                    self._refit_linear_leaves(tree, lp, gc, hc, Xmat,
                                              cfg.linear_lambda, decay_rate,
                                              new_out)
                delta = tree.predict(Xmat) if tree.is_linear else tree.leaf_value[lp]
                if k > 1:
                    score[:, c] += delta
                else:
                    score += delta
        new_booster = Booster.__new__(Booster)
        new_booster.params = dict(self.params)
        new_booster.config = loaded.config
        new_booster.best_iteration = -1
        new_booster.best_score = {}
        new_booster._train_set = None
        new_booster._boosting = loaded
        return new_booster

    @staticmethod
    def _refit_linear_leaves(tree, lp, g, h, Xmat, linear_lambda, decay_rate,
                             new_out) -> None:
        """Decay-blend linear leaf const/coeffs toward a fresh per-leaf ridge
        fit on the refit data (linear_tree_learner.cpp is_refit path; leaves
        with too few usable rows fall back to the blended plain output with
        zeroed coefficients, :323-329)."""
        shrink = tree.shrinkage
        for li in range(tree.num_leaves):
            feats = tree.leaf_features[li] if li < len(tree.leaf_features) else []
            old_coeffs = (tree.leaf_coeff[li]
                          if li < len(tree.leaf_coeff) else [])
            rows = lp == li
            Xl = (Xmat[rows][:, feats] if feats
                  else np.zeros((int(rows.sum()), 0)))
            ok = ~(np.isnan(Xl).any(axis=1) | np.isinf(Xl).any(axis=1)) \
                if feats else np.ones(int(rows.sum()), bool)
            if ok.sum() < len(feats) + 1:
                tree.leaf_const[li] = (decay_rate * tree.leaf_const[li]
                                       + (1.0 - decay_rate) * new_out[li])
                tree.leaf_coeff[li] = [0.0] * len(feats)
                continue
            X1 = np.concatenate([Xl[ok], np.ones((int(ok.sum()), 1))], axis=1)
            hl = h[rows][ok]
            gl = g[rows][ok]
            A = X1.T @ (X1 * hl[:, None])
            A[np.arange(len(feats)), np.arange(len(feats))] += linear_lambda
            try:
                sol = -np.linalg.solve(A, X1.T @ gl)
            except np.linalg.LinAlgError:
                sol = -(np.linalg.pinv(A) @ (X1.T @ gl))
            tree.leaf_coeff[li] = [
                decay_rate * (old_coeffs[i] if i < len(old_coeffs) else 0.0)
                + (1.0 - decay_rate) * float(sol[i]) * shrink
                for i in range(len(feats))]
            tree.leaf_const[li] = (decay_rate * tree.leaf_const[li]
                                   + (1.0 - decay_rate) * float(sol[-1]) * shrink)

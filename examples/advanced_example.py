"""Categorical features, callbacks, continued training, importance, SHAP."""
import numpy as np
import lightgbm_tpu as lgb

rng = np.random.RandomState(3)
n = 1500
X = rng.normal(size=(n, 6))
cat = rng.randint(0, 5, size=n).astype(float)        # categorical column
X = np.column_stack([X, cat])
y = (X[:, 0] + (cat == 2) * 1.5 + rng.normal(scale=0.3, size=n) > 0.5).astype(float)

params = {"objective": "binary", "num_leaves": 31, "verbosity": -1}
train = lgb.Dataset(X, label=y, params=params, categorical_feature=[6])

evals = {}
booster = lgb.train(
    params, train, 25,
    valid_sets=[train], valid_names=["train"],
    callbacks=[lgb.record_evaluation(evals),
               lgb.reset_parameter(learning_rate=lambda i: 0.1 * 0.98 ** i)])

# continued training from the in-memory model (init_model)
booster2 = lgb.train(params, lgb.Dataset(X, label=y, params=params,
                                         categorical_feature=[6]),
                     10, init_model=booster)
print("total trees after continuation:", booster2.num_trees())

imp = booster.feature_importance("gain")
print("gain importance (categorical col is #6):",
      np.round(imp / imp.sum(), 3))

dump = booster.dump_model()   # already a dict (json.dumps to serialize)
print("JSON dump trees:", len(dump["tree_info"]))

contrib = booster.predict(X[:5], pred_contrib=True)
print("SHAP row sums match raw scores:",
      np.allclose(contrib.sum(axis=1), booster.predict(X[:5], raw_score=True),
                  rtol=1e-4))

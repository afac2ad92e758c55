"""Importance / metric / tree plotting saved to PNG."""
import numpy as np
import lightgbm_tpu as lgb

try:
    import matplotlib
    matplotlib.use("Agg")
except ImportError:
    print("matplotlib not installed; skipping plot example")
    raise SystemExit(0)

rng = np.random.RandomState(5)
X = rng.normal(size=(1500, 6))
y = (X[:, 0] - X[:, 1] > 0).astype(float)
train = lgb.Dataset(X[:1200], label=y[:1200])
valid = lgb.Dataset(X[1200:], label=y[1200:], reference=train)
evals = {}
booster = lgb.train({"objective": "binary", "metric": "binary_logloss",
                     "num_leaves": 15, "verbosity": -1},
                    train, 30, valid_sets=[valid], valid_names=["valid"],
                    callbacks=[lgb.record_evaluation(evals)])

lgb.plot_importance(booster).figure.savefig("/tmp/lgb_importance.png")
lgb.plot_metric(evals, metric="binary_logloss").figure.savefig("/tmp/lgb_metric.png")
lgb.plot_tree(booster, tree_index=0).figure.savefig("/tmp/lgb_tree.png")
print("wrote /tmp/lgb_importance.png /tmp/lgb_metric.png /tmp/lgb_tree.png")

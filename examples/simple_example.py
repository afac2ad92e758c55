"""Train / validate / early-stop / predict / save — the minimum loop."""
import numpy as np
import lightgbm_tpu as lgb

rng = np.random.RandomState(7)
X = rng.normal(size=(2000, 10))
y = (X[:, 0] + 0.6 * X[:, 1] - 0.4 * X[:, 2] + rng.normal(scale=0.4, size=2000) > 0).astype(float)
Xtr, Xva, ytr, yva = X[:1600], X[1600:], y[:1600], y[1600:]

train = lgb.Dataset(Xtr, label=ytr)
valid = lgb.Dataset(Xva, label=yva, reference=train)

evals = {}
booster = lgb.train(
    {"objective": "binary", "metric": ["auc", "binary_logloss"],
     "num_leaves": 31, "learning_rate": 0.1, "verbosity": -1},
    train, num_boost_round=120,
    valid_sets=[valid], valid_names=["valid"],
    callbacks=[lgb.early_stopping(stopping_rounds=10),
               lgb.record_evaluation(evals)])

print(f"best iteration: {booster.best_iteration}")
print(f"valid AUC at best: {evals['valid']['auc'][booster.best_iteration - 1]:.4f}")

pred = booster.predict(Xva, num_iteration=booster.best_iteration)
print("accuracy:", float(np.mean((pred > 0.5) == (yva > 0.5))))

booster.save_model("/tmp/simple_model.txt")
reloaded = lgb.Booster(model_file="/tmp/simple_model.txt")
assert np.allclose(reloaded.predict(Xva[:10]), pred[:10], rtol=1e-6)
print("saved, reloaded, predictions match")

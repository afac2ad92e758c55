"""The sklearn-style estimator wrappers."""
import numpy as np
from lightgbm_tpu import LGBMClassifier, LGBMRegressor

rng = np.random.RandomState(1)
X = rng.normal(size=(2000, 8))
y_reg = X[:, 0] * 2 + np.sin(X[:, 1]) + rng.normal(scale=0.1, size=2000)
y_clf = (y_reg > 0.3).astype(int)

reg = LGBMRegressor(n_estimators=50, num_leaves=31, learning_rate=0.1)
reg.fit(X[:1600], y_reg[:1600],
        eval_set=[(X[1600:], y_reg[1600:])],
        callbacks=[])
print("regressor R^2 on held-out:", round(reg.score(X[1600:], y_reg[1600:]), 4))

clf = LGBMClassifier(n_estimators=50, num_leaves=31)
clf.fit(X[:1600], y_clf[:1600])
proba = clf.predict_proba(X[1600:])
acc = float(np.mean(clf.predict(X[1600:]) == y_clf[1600:]))
print("classifier accuracy:", round(acc, 4), "| proba shape:", proba.shape)
assert acc > 0.85

"""Distributed training over pre-partitioned parts (the dask-analog flow,
run locally: each worker process sees ONLY its own partition).

The __main__ guard is required: worker processes are spawned with
multiprocessing's spawn start method, which re-imports this module.
"""
import numpy as np
import lightgbm_tpu as lgb


def main():
    rng = np.random.RandomState(11)
    n = 2000
    X = rng.normal(size=(n, 6))
    y = (X[:, 0] + 0.5 * X[:, 1] > 0).astype(float)

    parts = [{"data": X[: n // 2], "label": y[: n // 2]},
             {"data": X[n // 2:], "label": y[n // 2:]}]
    booster = lgb.distributed.train_distributed(
        {"objective": "binary", "num_leaves": 15, "min_data_in_leaf": 5,
         "verbosity": -1},
        parts, num_boost_round=10,
        devices_per_proc=4)   # 4 virtual CPU devices per worker for the demo

    pred = booster.predict(X[:8])
    print("distributed model trained;", booster.num_trees(), "trees;",
          "sample predictions:", np.round(pred, 3))


if __name__ == "__main__":
    main()

"""lightgbm_tpu: a TPU-native gradient-boosting framework.

A from-scratch re-design of the capabilities of LightGBM (reference at
/root/reference, v3.2.1.99) for TPU hardware: JAX/XLA for the training
dataflow (binning -> per-leaf histograms -> split search -> partition ->
score update as jitted programs), jax.sharding/shard_map for distributed
training over device meshes, and a Python API mirroring the reference's
python-package surface (Dataset/Booster/train/cv/sklearn wrappers).
"""

import time as _time

_IMPORT_T0_NS = _time.time_ns()    # the span "import" begins (profiling.py)

from . import checkpoint, distributed, supervisor   # noqa: E402
from .basic import Dataset
from .booster import Booster
from .callback import (EarlyStopException, early_stopping, log_evaluation,
                       print_evaluation, record_evaluation, reset_parameter)
# the checkpoint CALLBACK exports as checkpoint_callback: the bare name
# `checkpoint` is bound (by the explicit submodule import above) to the
# lightgbm_tpu.checkpoint submodule (CheckpointManager and friends)
from .callback import checkpoint as checkpoint_callback
from .config import Config
from .distributed import DistributedTimeoutError
from .engine import CVBooster, cv, train
from .serving import (ServeFrontend, ServeOverloadError, ServeSwapError,
                      ServeTimeoutError)
from .utils.log import register_logger

__version__ = "0.1.0"

__all__ = [
    "Dataset", "Booster", "Config", "train", "cv", "CVBooster",
    "register_logger", "early_stopping", "print_evaluation", "log_evaluation",
    "record_evaluation", "reset_parameter", "EarlyStopException",
    "checkpoint_callback", "DistributedTimeoutError",
    "ServeFrontend", "ServeTimeoutError", "ServeOverloadError",
    "ServeSwapError",
]


def __getattr__(name):
    # lazy sklearn-API exports (mirrors python-package/lightgbm/__init__.py)
    if name in ("LGBMModel", "LGBMRegressor", "LGBMClassifier", "LGBMRanker"):
        from . import sklearn as _sk
        return getattr(_sk, name)
    if name in ("plot_importance", "plot_metric", "plot_tree",
                "plot_split_value_histogram", "create_tree_digraph"):
        from . import plotting as _pl
        return getattr(_pl, name)
    raise AttributeError(f"module 'lightgbm_tpu' has no attribute {name!r}")


from .utils import profiling as _profiling     # noqa: E402

_profiling.record_span("import", _IMPORT_T0_NS, _time.time_ns())

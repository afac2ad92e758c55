"""Training entry points: train() and cv().

Mirrors the reference's Python engine (reference:
python-package/lightgbm/engine.py:14-470): parameter munging, the
callbacks-before/after-iteration protocol, early stopping via
``EarlyStopException`` (engine.py:244-272), and stratified/group-aware CV
folds (engine.py:281-470).
"""

from __future__ import annotations

import copy
from typing import Any, Dict, List, Optional

import numpy as np

from . import callback as callback_mod
from .basic import Dataset
from .booster import Booster
from .callback import CallbackEnv, EarlyStopException
from .config import PARAM_ALIASES
from .utils import log, profiling


def _resolve_num_boost_round(params: Dict[str, Any], num_boost_round: int) -> int:
    for alias, canonical in PARAM_ALIASES.items():
        if canonical == "num_iterations" and alias in params:
            return int(params.pop(alias))
    return int(params.pop("num_iterations", num_boost_round))


def train(params: Dict[str, Any], train_set: Dataset, num_boost_round: int = 100,
          valid_sets: Optional[List[Dataset]] = None,
          valid_names: Optional[List[str]] = None,
          fobj=None, feval=None, init_model=None,
          feature_name="auto", categorical_feature="auto",
          early_stopping_rounds: Optional[int] = None,
          evals_result: Optional[dict] = None,
          verbose_eval="warn", learning_rates=None,
          keep_training_booster: bool = False, callbacks=None,
          resume_from: Optional[str] = None) -> Booster:
    """Train a booster (reference: engine.py:14-278).

    ``resume_from``: a checkpoint directory written by the
    ``callback.checkpoint`` callback — training restores the full trainer
    state (trees, score caches, RNG/drop state, eval history, early-stop
    counters) from the newest VALID checkpoint and continues at the saved
    iteration, reproducing the uninterrupted run bit-identically; when the
    directory holds no valid checkpoint, training starts from scratch with
    a warning. Pass the same params/datasets/callbacks as the original run
    (a params or dataset mismatch is rejected)."""
    params = copy.deepcopy(params)
    num_boost_round = _resolve_num_boost_round(params, num_boost_round)
    if fobj is not None:
        params["objective"] = "none"
    if feature_name != "auto":
        train_set.feature_name = feature_name
    if categorical_feature != "auto":
        train_set.categorical_feature = categorical_feature

    first_metric_only = params.get("first_metric_only", False)

    # continued training (reference: engine.py:163-169 — the init model's
    # predictions seed the score caches, and its trees stay in the ensemble)
    loaded = None
    if init_model is not None:
        from .config import Config
        from .io.model_text import load_model
        if isinstance(init_model, Booster):
            loaded = load_model(init_model.model_to_string(),
                                Config.from_params(params))
        else:
            with open(init_model) as fh:
                loaded = load_model(fh.read(), Config.from_params(params))
        if loaded.num_trees > 0:
            if train_set.data is None:
                log.fatal("Cannot use init_model with a Dataset whose raw "
                          "data was freed")
            # pandas category columns must map through the SAME category ->
            # code lists as the init model, or the loaded trees' thresholds
            # silently misalign with the new Dataset's codes (reference:
            # basic.py train/predict pandas_categorical contract)
            pc = {int(k): list(v)
                  for k, v in (loaded.meta.get("pandas_categorical")
                               or {}).items()}
            if pc:
                if train_set._constructed:
                    if {int(k): list(v)
                            for k, v in train_set.pandas_categorical.items()} \
                            != pc:
                        log.fatal(
                            "train and init_model pandas categorical columns "
                            "do not match: construct the training Dataset "
                            "from data with the same category lists")
                else:
                    train_set.pandas_categorical = pc
            train_set.init_score = loaded.predict_raw(train_set.data)
            for vs in (valid_sets or []):
                if vs is train_set:
                    continue
                if vs.data is None:
                    log.fatal("Cannot use init_model with a validation "
                              "Dataset whose raw data was freed")
                vs.init_score = loaded.predict_raw(vs.data)

    # construct the training data BEFORE the booster (Dataset.construct
    # opens the span and the TIMETAG scope "construct" itself; streaming
    # construction nests its sketch_pass / bin_pass / h2d_overlap under
    # it), replicating Booster.__init__'s exact pre-construct protocol:
    # params merge first (max_bin etc. in TRAIN params must reach
    # binning), then the multi-machine bootstrap. A pre-constructed
    # (load_partitioned) dataset no-ops through.
    if not train_set._constructed:
        from . import distributed
        from .config import Config
        merged = dict(train_set.params or {})
        merged.update(params)
        train_set.params = merged
        distributed.maybe_init_from_config(Config.from_params(params))
        train_set.construct()
    booster = Booster(params=params, train_set=train_set)
    if loaded is not None and loaded.num_trees > 0:
        booster._boosting.loaded = loaded
        booster._boosting.loaded_iters = loaded.num_iteration
    valid_sets = valid_sets or []
    valid_names = valid_names or []
    for i, vs in enumerate(valid_sets):
        if vs is train_set:
            booster._boosting.config.is_provide_training_metric = True
            continue
        name = valid_names[i] if i < len(valid_names) else f"valid_{i}"
        if vs.reference is None:
            vs.reference = train_set
        booster.add_valid(vs, name)

    cbs = set(callbacks or [])
    if verbose_eval is True or (isinstance(verbose_eval, int) and not isinstance(verbose_eval, bool)):
        period = 1 if verbose_eval is True else verbose_eval
        cbs.add(callback_mod.print_evaluation(period))
    if early_stopping_rounds is not None and early_stopping_rounds > 0:
        import jax
        if (getattr(train_set, "is_pre_partitioned", False)
                and jax.process_count() > 1):
            # metrics evaluate on each process's LOCAL partition (the
            # reference's per-machine metric semantics): local values
            # differ, so per-process stopping decisions would desync the
            # SPMD collectives and hang
            log.fatal("early_stopping_rounds is not supported with "
                      "multi-process pre-partitioned training: metrics "
                      "are per-process local, so stopping decisions would "
                      "diverge across processes")
        cbs.add(callback_mod.early_stopping(early_stopping_rounds, first_metric_only))
    if evals_result is not None:
        cbs.add(callback_mod.record_evaluation(evals_result))
    if learning_rates is not None:
        cbs.add(callback_mod.reset_parameter(learning_rate=learning_rates))

    cbs_before = sorted((c for c in cbs if getattr(c, "before_iteration", False)),
                        key=lambda c: getattr(c, "order", 0))
    cbs_after = sorted((c for c in cbs if not getattr(c, "before_iteration", False)),
                       key=lambda c: getattr(c, "order", 0))
    # the checkpoint callback captures stateful-callback state through the
    # booster (checkpoint.capture_state reads booster._callbacks)
    booster._callbacks = cbs_before + cbs_after

    start_iter = 0
    if resume_from is not None:
        # pre-partitioned runs resume from SHARDED checkpoints: each rank
        # reassembles its process-local score caches from the shard files
        # under the current partition (checkpoint.restore_booster), so the
        # gang may even come back at a different world size; a legacy
        # rank-0-only checkpoint is rejected there with a clear error.
        from . import checkpoint as checkpoint_mod
        ckpt = checkpoint_mod.CheckpointManager(resume_from).load_latest_valid()
        if ckpt is None:
            log.warning(f"resume_from={resume_from!r}: no valid checkpoint "
                        f"found; training from scratch")
        else:
            cb_states = checkpoint_mod.restore_booster(booster, ckpt)
            start_iter = int(ckpt.state["boosting"]["iter"])
            for cb in booster._callbacks:
                key = getattr(cb, "ckpt_key", None)
                if key in cb_states and hasattr(cb, "set_state"):
                    cb.set_state(cb_states[key])
            log.info(f"resumed from checkpoint {ckpt.path} at iteration "
                     f"{start_iter}")
            from . import compile_cache
            if getattr(booster.config, "compile_warmup", True) \
                    and compile_cache.configure(booster.config):
                # AOT-warm the training programs NOW, before the loop:
                # with the persistent compilation cache a restarted
                # incarnation deserializes the fused step from disk here
                # and reaches its first iteration with zero XLA compiles.
                # ONLY with a cache configured — without one the warmup
                # would just pay the first iteration's compile here
                booster._boosting.warm_start()

    from . import distributed
    from .utils import faults
    fault_plan = faults.plan_from(booster.config)
    # training supervision: heartbeat (multi-process liveness) and the
    # collective_deadline watchdog — a dead/hung peer must surface as a
    # diagnosable DistributedTimeoutError (or a supervised gang restart),
    # never an indefinite collective stall
    health = distributed.start_health(booster.config)
    # cross-rank divergence detection (the training-integrity layer): every
    # integrity_check_period iterations the ranks exchange a model-state
    # fingerprint and majority-vote mismatches — run BEFORE the after-
    # iteration callbacks so a checkpoint is never written from state the
    # gang has already voted corrupt. No-op single-process / when 0.
    import jax
    integ_period = int(getattr(booster.config, "integrity_check_period", 0)
                       or 0)
    integ_on = integ_period > 0 and jax.process_count() > 1
    boosting = booster._boosting
    # --- K-iterations-per-dispatch handshake (boost_rounds_per_dispatch):
    # only THIS loop may let one update() consume a whole K-block (it
    # advances its round counter by the consumed count below); a manual
    # Booster.update loop or cv() never opts in and keeps per-iteration
    # semantics. Callbacks/eval run at block boundaries, so:
    #   - a checkpoint callback period must be a multiple of K (a
    #     mid-block checkpoint cannot exist — the block is one atomic
    #     dispatch — so misaligned periods are REJECTED, loudly);
    #   - per-iteration parameter schedules (reset_parameter /
    #     learning_rates) disable blocking for the run — their values
    #     must apply per iteration, not per block.
    k_block = max(1, int(getattr(booster.config,
                                 "boost_rounds_per_dispatch", 1)))
    if k_block > 1 and hasattr(boosting, "_block_rounds"):
        # the schedule fallback is decided FIRST: with blocking disabled
        # the run is per-iteration, where any checkpoint period is valid
        # — rejecting it would refuse a run that executes fine
        if any(getattr(cb, "is_reset_parameter", False)
               for cb in cbs_before):
            log.info(f"boost_rounds_per_dispatch={k_block} disabled for "
                     f"this run: a reset_parameter/learning_rates "
                     f"callback applies per-iteration values the block "
                     f"dispatch cannot honor")
            boosting._block_disable = True
        else:
            for cb in (cbs_before + cbs_after):
                p = getattr(cb, "ckpt_period", None)
                if p and p > 0 and p % k_block != 0:
                    log.fatal(
                        f"checkpoint period {p} is not a multiple of "
                        f"boost_rounds_per_dispatch={k_block}: a "
                        f"K-iteration block is one atomic dispatch, so a "
                        f"mid-block checkpoint cannot be captured. Use a "
                        f"period that is a multiple of {k_block}, or set "
                        f"boost_rounds_per_dispatch=1.")
        boosting._block_target = num_boost_round
    try:
        i = start_iter
        while i < num_boost_round:
            faults.maybe_kill(fault_plan, i)
            faults.maybe_hang(fault_plan, i)
            with profiling.span("callbacks"):
                for cb in cbs_before:
                    cb(CallbackEnv(model=booster, params=params, iteration=i,
                                   begin_iteration=0,
                                   end_iteration=num_boost_round,
                                   evaluation_result_list=None))
            it_before = boosting.iter
            booster.update(fobj=fobj)
            # a K-block consumes several iterations in one update() —
            # advance by what actually happened (1 everywhere else)
            consumed = max(1, boosting.iter - it_before)
            i += consumed
            # fire whenever a period boundary was CROSSED in the consumed
            # span, not only when i lands exactly on one — today blocks
            # cannot engage multi-process (fused requires one process),
            # but this keeps the divergence-check frequency exact if that
            # ever changes
            if integ_on and (i // integ_period) > \
                    ((i - consumed) // integ_period):
                distributed.check_model_integrity(boosting, i - 1)

            evaluation_result_list = []
            if valid_sets or boosting.config.is_provide_training_metric:
                with profiling.span("eval"):
                    evaluation_result_list = booster.eval_set(feval)
            try:
                with profiling.span("callbacks"):
                    for cb in cbs_after:
                        cb(CallbackEnv(
                            model=booster, params=params, iteration=i - 1,
                            begin_iteration=0, end_iteration=num_boost_round,
                            evaluation_result_list=evaluation_result_list))
            except EarlyStopException as es:
                booster.best_iteration = es.best_iteration + 1
                for item in es.best_score:
                    booster.best_score.setdefault(item[0], {})[item[1]] = item[2]
                break
        # judge every still-deferred numerics sentinel (the fused path's
        # flag words are fetched lazily; without this flush a NaN born in
        # the final rounds could go unreported)
        boosting._flush_sentinel()
    except BaseException as e:
        # a dying run flushes its flight recorder (telemetry.py): the
        # per-iteration ring + this reason are the post-mortem — the NaN
        # sentinel verdict, watchdog diagnosis or OOM ladder history is
        # on disk before the exception unwinds. THIS booster's recorder,
        # not the module slot: in multi-booster processes (cv folds) the
        # module slot holds the last-configured booster's ring.
        if hasattr(boosting, "_flush_flight"):
            boosting._close_flight()
            boosting._flush_flight(
                f"train-error: {type(e).__name__}: {str(e)[:300]}")
        raise
    finally:
        boosting._block_target = None
        health.stop()
        # training ends: the last iteration's record is closed here (its
        # callbacks and eval belong to it)
        if hasattr(boosting, "_close_flight"):
            boosting._close_flight()
    # clean end: flush only when a durable telemetry dir was configured
    # (telemetry_dir / supervised diag dir / checkpoint_path) — ordinary
    # runs must not litter temp dirs with post-mortems nobody asked for
    fr = getattr(boosting, "_flight", None)
    if fr is not None and fr.directory:
        # the file is the run's record: wait for the last trees, so that
        # every iteration carries its rows streamed
        boosting._flush_pending()
        fr.flush("train-end")
    return booster


class CVBooster:
    """Ensemble of per-fold boosters (reference: engine.py:281-317)."""

    def __init__(self):
        self.boosters: List[Booster] = []
        self.best_iteration = -1

    def _append(self, booster: Booster) -> None:
        self.boosters.append(booster)

    def __getattr__(self, name):
        def handler_function(*args, **kwargs):
            return [getattr(b, name)(*args, **kwargs) for b in self.boosters]
        return handler_function


def _make_n_folds(full_data: Dataset, folds, nfold: int, params: Dict[str, Any],
                  seed: int, stratified: bool, shuffle: bool):
    """reference: engine.py:319-376 _make_n_folds."""
    full_data.construct()
    num_data = full_data.num_data
    if folds is not None:
        if not hasattr(folds, "__iter__") and hasattr(folds, "split"):
            group = full_data.get_group()
            if group is not None:
                group_idx = np.repeat(np.arange(len(group)), group)
                folds = folds.split(X=np.empty(num_data), groups=group_idx)
            else:
                folds = folds.split(X=np.empty(num_data))
        return list(folds)
    rng = np.random.RandomState(seed)
    label = full_data.get_label()
    if stratified:
        # stratified fold assignment by label
        idx = np.arange(num_data)
        assignment = np.zeros(num_data, dtype=np.int64)
        for lv in np.unique(label):
            sel = idx[label == lv]
            if shuffle:
                rng.shuffle(sel)
            assignment[sel] = np.arange(len(sel)) % nfold
    else:
        idx = np.arange(num_data)
        if shuffle:
            rng.shuffle(idx)
        assignment = np.zeros(num_data, dtype=np.int64)
        assignment[idx] = np.arange(num_data) % nfold
    out = []
    for f in range(nfold):
        test_idx = np.nonzero(assignment == f)[0]
        train_idx = np.nonzero(assignment != f)[0]
        out.append((train_idx, test_idx))
    return out


def _agg_cv_result(raw_results):
    """reference: engine.py:378-390."""
    cvmap = {}
    metric_type = {}
    for one_result in raw_results:
        for one_line in one_result:
            key = f"{one_line[0]} {one_line[1]}"
            metric_type[key] = one_line[3]
            cvmap.setdefault(key, []).append(one_line[2])
    return [("cv_agg", k, float(np.mean(v)), metric_type[k], float(np.std(v)))
            for k, v in cvmap.items()]


def cv(params: Dict[str, Any], train_set: Dataset, num_boost_round: int = 100,
       folds=None, nfold: int = 5, stratified: bool = True, shuffle: bool = True,
       metrics=None, fobj=None, feval=None, init_model=None,
       feature_name="auto", categorical_feature="auto",
       early_stopping_rounds=None, fpreproc=None, verbose_eval=None,
       show_stdv: bool = True, seed: int = 0, callbacks=None,
       eval_train_metric: bool = False,
       return_cvbooster: bool = False) -> Dict[str, List[float]]:
    """Cross-validation (reference: engine.py:392-470)."""
    params = copy.deepcopy(params)
    num_boost_round = _resolve_num_boost_round(params, num_boost_round)
    if fobj is not None:
        params["objective"] = "none"
    if metrics is not None:
        params["metric"] = metrics
    if params.get("objective") in ("binary",) or str(params.get("objective", "")).startswith("multiclass"):
        pass
    else:
        stratified = False

    folds = _make_n_folds(train_set, folds, nfold, params, seed, stratified, shuffle)
    cvbooster = CVBooster()
    fold_data = []
    for train_idx, test_idx in folds:
        tr = train_set.subset(train_idx)
        te = train_set.subset(test_idx)
        if fpreproc is not None:
            tr, te, params = fpreproc(tr, te, params.copy())
        fold_data.append((tr, te))

    results: Dict[str, List[float]] = {}
    boosters = []
    for tr, te in fold_data:
        b = Booster(params=params, train_set=tr)
        b.add_valid(te, "valid")
        boosters.append(b)
        cvbooster._append(b)

    cbs = set(callbacks or [])
    if early_stopping_rounds is not None and early_stopping_rounds > 0:
        cbs.add(callback_mod.early_stopping(early_stopping_rounds, verbose=False))
    if verbose_eval:
        period = 1 if verbose_eval is True else int(verbose_eval)
        cbs.add(callback_mod.print_evaluation(period, show_stdv))
    cbs_after = sorted((c for c in cbs if not getattr(c, "before_iteration", False)),
                       key=lambda c: getattr(c, "order", 0))

    for i in range(num_boost_round):
        raw = []
        for b in boosters:
            b.update(fobj=fobj)
            if eval_train_metric:
                raw.append(b.eval_set(feval))
            else:
                raw.append(b.eval_valid(feval))
        agg = _agg_cv_result(raw)
        for _, key, mean, _, std in agg:
            results.setdefault(f"{key}-mean", []).append(mean)
            results.setdefault(f"{key}-stdv", []).append(std)
        try:
            for cb in cbs_after:
                cb(CallbackEnv(model=cvbooster, params=params, iteration=i,
                               begin_iteration=0, end_iteration=num_boost_round,
                               evaluation_result_list=agg))
        except EarlyStopException as es:
            cvbooster.best_iteration = es.best_iteration + 1
            for k in list(results.keys()):
                results[k] = results[k][:cvbooster.best_iteration]
            break
    if return_cvbooster:
        results["cvbooster"] = cvbooster
    return results

"""Atomic checkpoint/resume for fault-tolerant training.

The reference's only mid-training persistence is ``snapshot_freq`` model
dumps (gbdt.cpp:277-281): non-atomic in-place writes that lose all trainer
state — DART's drop RNG, the feature-fraction RNG, bagging phase, eval
history, early-stopping counters — so a "resume" from one silently trains
a DIFFERENT model. This module makes resumable boosting a design point
(the TF Boosted Trees stance, arXiv:1710.11555): a checkpoint captures
the model text PLUS a trainer-state sidecar, every file lands via
``utils/atomic_write`` (tmp + fsync + rename), and a manifest written
LAST records byte lengths + sha256 checksums so a kill at any point
leaves either a fully valid checkpoint or one that validation rejects.

Layout under the checkpoint directory::

    ckpt_00000007/
        model.txt       v3 model text (interop: loads as a normal model)
        state.pkl       pickled trainer state (trees, scores, RNGs, ...)
        MANIFEST.json   iteration, params hash, dataset fingerprint,
                        per-file {bytes, sha256}; its presence marks the
                        checkpoint complete

``load_latest_valid`` walks checkpoints newest-first and falls back past
any truncated/corrupt one with a warning. Resume is BIT-IDENTICAL: the
sidecar restores the exact float32 score caches, device tree arrays and
RNG states, so kill-at-k + resume reproduces the uninterrupted run's
model text byte for byte (tests/test_fault_tolerance.py asserts this for
gbdt/dart/goss with bagging).

Multi-process runs write from rank 0 only, with a cross-process barrier
after the save so no rank races ahead of a checkpoint that may later be
resumed from.

Note: ``state.pkl`` is a pickle — load checkpoints only from directories
you trust, like any model artifact.
"""

from __future__ import annotations

import hashlib
import json
import os
import pickle
import re
import shutil
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Tuple

from .utils import log
from .utils import faults
from .utils.atomic_write import atomic_write_bytes, atomic_write_text

MANIFEST_NAME = "MANIFEST.json"
MODEL_NAME = "model.txt"
STATE_NAME = "state.pkl"
PARTITION_NAME = "PARTITION.json"
_CKPT_RE = re.compile(r"^ckpt_(\d{8})$")
MANIFEST_FORMAT = 1


def shard_name(rank: int) -> str:
    """Per-rank score-cache shard file inside a sharded checkpoint."""
    return f"shard_rank{int(rank)}.pkl"

# params that steer IO/logging/injection but not the trained model — they
# may differ between the checkpointing run and the resuming run
_NON_TRAINING_PARAMS = frozenset({
    "task", "data", "valid", "input_model", "output_model", "output_result",
    "convert_model", "convert_model_language", "verbosity", "snapshot_freq",
    "metric_freq", "num_threads", "machine_list_filename",
    "checkpoint_path", "checkpoint_keep", "checkpoint_shards",
    "check_numerics",
    # hist_pallas_interpret is NOT here: off-TPU it changes which
    # algorithm "auto" resolves to (scatter vs the hilo kernel), i.e. the
    # histogram rounding model — the same class of drift as
    # histogram_method itself, which is hashed. quantized_grad is NOT
    # here — it changes the trained model.
    # split_fusion is bit-identical to the classic split phase by
    # contract (tests/test_split_fusion.py pins model-text parity), so
    # toggling it between incarnations is execution strategy, not model
    # drift
    "split_fusion",
    "heartbeat_interval", "collective_deadline", "max_restarts",
    "rank_restart_budget", "min_world_size",
    # training-integrity knobs: the divergence-check cadence and the OOM
    # fallback GATE steer supervision, not the trained model (a degrade
    # EVENT does change numerics — which is why the degraded configuration
    # itself rides the trainer state, see GBDT.get_trainer_state
    # "oom_degrade" — but toggling the gate between runs must not reject
    # an otherwise-valid resume)
    "integrity_check_period", "hist_oom_fallback",
    # serving-front-end knobs: batching/deadline/admission policy for the
    # ServeFrontend — pure request-routing, never touches training
    "serve_flush_ms", "serve_max_batch_rows", "serve_max_queue_rows",
    "serve_deadline_ms", "serve_metrics", "serve_metrics_port",
    "serve_metrics_host",
    # telemetry knobs (lightgbm_tpu/telemetry.py): the flight recorder
    # observes training from already-fetched host values — ring size,
    # flush cadence and destination can all differ between the
    # checkpointing run and the resuming run without touching the model
    "telemetry_flight_recorder", "telemetry_ring_size", "telemetry_dir",
    "telemetry_flush_period", "telemetry_memory",
    "fault_kill_at_iter", "fault_hang_at_iter", "fault_kill_in_ckpt_write",
    "fault_nan_grad_at_iter", "fault_corrupt_checkpoint",
    "fault_kill_rank_at_iter", "fault_hang_rank_at_iter",
    "fault_kill_in_shard_write", "fault_corrupt_shard",
    "fault_flip_score_rank", "fault_nan_hist_at_iter",
    "fault_oom_at_iter", "fault_oom_count",
    "fault_slow_predict_ms", "fault_oom_at_predict",
})


def params_hash(config) -> str:
    """Stable hash of the training-relevant parameters: resuming under a
    different configuration must be detected, not silently train a
    different model. Walks the full Config field set directly —
    ``to_params()`` omits list-typed fields (default_factory), which would
    blind the check to monotone/interaction constraints, per-feature bins,
    metric lists etc."""
    import dataclasses
    items = sorted(
        (f.name, repr(getattr(config, f.name)))
        for f in dataclasses.fields(type(config))
        if f.name not in _NON_TRAINING_PARAMS)
    return hashlib.sha256(repr(items).encode()).hexdigest()[:16]


def dataset_fingerprint(train_set, local: bool = False) -> str:
    """Cheap identity check for the training data: shape plus label/weight
    bytes (not a full data hash — the point is catching 'resumed on a
    different dataset', not bit-auditing features). With ``local`` the
    shape part uses the PROCESS-LOCAL row count (labels/weights are
    already process-local on pre-partitioned datasets), giving the
    per-rank fingerprint sharded manifests record."""
    import numpy as np
    h = hashlib.sha256()
    n_local = getattr(train_set, "num_local_data", None) if local else None
    n = int(n_local if n_local is not None
            else (getattr(train_set, "num_data", 0) or 0))
    f = int(getattr(train_set, "num_total_features", 0) or 0)
    h.update(f"{n}x{f}".encode())
    label = train_set.get_label() if hasattr(train_set, "get_label") else None
    if label is not None:
        h.update(np.ascontiguousarray(np.asarray(label, np.float64)).tobytes())
    weight = train_set.get_weight() if hasattr(train_set, "get_weight") else None
    if weight is not None:
        h.update(np.ascontiguousarray(np.asarray(weight, np.float64)).tobytes())
    return h.hexdigest()[:16]


def label_range_sha256(label, lo: int, hi: int) -> str:
    """sha256 of LOCAL label rows [lo, hi) as float64 bytes — the per-rank
    row-content hash PARTITION.json records, recomputable by any later
    rank whose local range CONTAINS [lo, hi)."""
    import numpy as np
    a = np.ascontiguousarray(np.asarray(label, np.float64)[lo:hi])
    return hashlib.sha256(a.tobytes()).hexdigest()


def split_local_state(state: Dict[str, Any]) -> Tuple[Dict[str, Any],
                                                      Dict[str, Any]]:
    """Split a captured trainer state into (global, local) halves for the
    sharded layout: the score caches are the process-LOCAL rows of a
    pre-partitioned run and go into the rank's shard; everything else
    (trees, RNGs, counters) is rank-symmetric and lives in rank 0's
    state.pkl. The inverse is a plain dict merge before
    ``set_trainer_state``."""
    state = dict(state)
    boosting = dict(state["boosting"])
    local = {
        "train_score": boosting.pop("train_score"),
        "valid_scores": boosting.pop("valid_scores"),
    }
    state["boosting"] = boosting
    return state, local


def capture_state(booster) -> Dict[str, Any]:
    """Full trainer state of a training booster: the boosting layer's state
    (trees, score caches, RNGs — see GBDT.get_trainer_state) plus
    booster-level fields and the states of any stateful callbacks the
    engine registered on the booster."""
    state: Dict[str, Any] = {
        "format": MANIFEST_FORMAT,
        "boosting": booster._boosting.get_trainer_state(),
        "booster": {
            "best_iteration": booster.best_iteration,
            "best_score": dict(booster.best_score),
            "attr": dict(getattr(booster, "_attr", {}) or {}),
        },
        "callbacks": {},
    }
    for cb in getattr(booster, "_callbacks", []) or []:
        key = getattr(cb, "ckpt_key", None)
        if key and hasattr(cb, "get_state"):
            state["callbacks"][key] = cb.get_state()
    return state


@dataclass
class LoadedCheckpoint:
    path: str
    iteration: int
    manifest: Dict[str, Any]
    model_text: str
    state: Dict[str, Any]
    # sharded checkpoints only: the PARTITION.json row-partition manifest
    # ({"world_size", "global_rows", "ranks": [{"rank", "row_start",
    # "row_count", "label_sha256", "valid_counts"}, ...]}); the state above
    # is then the GLOBAL half (score caches live in the shards)
    partition: Optional[Dict[str, Any]] = None


class CheckpointManager:
    """Writes, validates, prunes and loads checkpoints in one directory."""

    def __init__(self, directory: str, keep: int = 2, config=None):
        self.directory = os.fspath(directory)
        self.keep = max(1, int(keep))
        self._fault_plan = faults.plan_from(config)
        self._dataset_fp: Optional[str] = None
        self._label_sha: Optional[str] = None

    # ------------------------------------------------------------- write
    def save(self, booster, iteration: int) -> Optional[str]:
        """Checkpoint ``booster`` after ``iteration`` completed boosting
        iterations. Replicated-data runs: rank 0 writes. Pre-partitioned
        runs (``checkpoint_shards``): EVERY rank writes its process-local
        score-cache shard and rank 0 publishes the manifests. Every rank
        barriers after, so no process races past a checkpoint another may
        resume from."""
        import jax
        from . import distributed
        path = None
        boosting = getattr(booster, "_boosting", None)
        sharded = bool(getattr(boosting, "_pre_part", False)) and \
            bool(getattr(booster.config, "checkpoint_shards", True))
        if sharded:
            path = self._write_sharded_booster(booster, iteration)
        elif jax.process_count() <= 1 or jax.process_index() == 0:
            path = self._write(booster, iteration)
        distributed.barrier(f"lgbm_tpu_checkpoint_{iteration}")
        return path

    def _write_sharded_booster(self, booster, iteration: int) -> Optional[str]:
        """Assemble the sharded-write inputs from a live pre-partitioned
        booster and run the rank-symmetric protocol (``write_sharded``)."""
        import jax
        import numpy as np
        boosting = booster._boosting
        ts = boosting.train_set
        if jax.process_index() == 0:
            state = capture_state(booster)
            global_state, local_state = split_local_state(state)
        else:
            # non-zero ranks contribute ONLY their score-cache shard:
            # capture_state would device_get the whole tree ensemble just
            # to be discarded (the global half is rank-symmetric and
            # written by rank 0 alone)
            global_state = {}
            local_state = {
                "train_score": np.asarray(boosting.train_score),
                "valid_scores": [np.asarray(s)
                                 for s in boosting._valid_scores],
            }
        row_start = int(getattr(ts, "local_row_start", 0) or 0)
        n_local = getattr(ts, "num_local_data", None)
        row_count = int(n_local if n_local is not None else ts.num_data)
        if self._dataset_fp is None:
            self._dataset_fp = dataset_fingerprint(ts, local=True)
        if self._label_sha is None:
            # labels are immutable after construction: hash once per
            # manager, not per checkpoint (O(n_local) f64 bytes)
            label = ts.get_label() if hasattr(ts, "get_label") else None
            self._label_sha = (label_range_sha256(label, 0, row_count)
                               if label is not None else "")
        label_sha = self._label_sha or None
        phash = getattr(booster, "_initial_params_hash", None) \
            or params_hash(booster.config)
        return self.write_sharded(
            iteration,
            # only rank 0 ever writes the model/global payloads — the
            # other ranks must not pay a full-ensemble serialization per
            # checkpoint
            model_text=(booster.model_to_string(num_iteration=-1)
                        if jax.process_index() == 0 else ""),
            global_state=global_state,
            local_state=local_state,
            row_start=row_start, row_count=row_count,
            global_rows=int(ts.num_data),
            fingerprint=self._dataset_fp,
            label_sha256=label_sha,
            valid_counts=[int(s.shape[0])
                          for s in local_state["valid_scores"]],
            phash=phash)

    def write_sharded(self, iteration: int, *, model_text: str,
                      global_state: Dict[str, Any],
                      local_state: Dict[str, Any],
                      row_start: int, row_count: int, global_rows: int,
                      fingerprint: str, label_sha256: Optional[str],
                      valid_counts: List[int],
                      phash: str = "") -> Optional[str]:
        """The rank-symmetric sharded checkpoint protocol. EVERY rank calls
        this in lockstep; all cross-rank coordination is the
        coordination-service ``distributed.exchange_host`` (pure gRPC — no
        XLA collectives, so the protocol runs on any backend):

        1. rank 0 stages ``ckpt_N.tmp`` (or decides to skip an
           already-valid ``ckpt_N``) and broadcasts the decision;
        2. every rank writes ``shard_rank{r}.pkl`` into the stage and
           exchanges its shard metadata (bytes, sha256, row range,
           fingerprint) — the exchange doubles as the all-shards-landed
           barrier;
        3. rank 0 writes model.txt, the GLOBAL state.pkl, PARTITION.json
           and (last) MANIFEST.json, then publishes with one rename.

        A rank killed at any point leaves either no ``ckpt_N`` (a stale
        ``.tmp`` readers ignore) or a complete one. Returns the published
        path on rank 0, None elsewhere."""
        import jax
        from . import distributed
        rank = jax.process_index()
        world = jax.process_count()
        name = f"ckpt_{iteration:08d}"
        path = os.path.join(self.directory, name)
        stage = path + ".tmp"
        # ---- decision: stage a new write, or skip an already-valid one
        decision = ""
        if rank == 0:
            os.makedirs(self.directory, exist_ok=True)
            self._clean_stale_tmp()
            if os.path.isdir(path) and self._quick_valid(path):
                decision = "skip"     # see _write: resume re-reached a
                                      # checkpointed iteration bit-identically
            else:
                if os.path.isdir(path):
                    shutil.rmtree(path, ignore_errors=True)
                os.makedirs(stage, exist_ok=True)
                decision = "stage"
        decision = distributed.exchange_host(
            f"ckpt_decision_{iteration}", decision)[0]
        if decision == "skip":
            if rank == 0:
                self._prune()
                return path
            return None
        # ---- every rank writes its shard, then exchanges its metadata
        shard_bytes = pickle.dumps(local_state, protocol=4)
        atomic_write_bytes(os.path.join(stage, shard_name(rank)),
                           shard_bytes)
        faults.maybe_kill_in_shard_write(self._fault_plan, iteration)
        meta = {
            "rank": rank,
            "bytes": len(shard_bytes),
            "sha256": hashlib.sha256(shard_bytes).hexdigest(),
            "row_start": int(row_start),
            "row_count": int(row_count),
            "fingerprint": fingerprint,
            "label_sha256": label_sha256,
            "valid_counts": [int(c) for c in valid_counts],
        }
        metas = [json.loads(m) for m in distributed.exchange_host(
            f"ckpt_shard_{iteration}", json.dumps(meta))]
        if rank != 0:
            return None
        # ---- rank 0: global payloads, partition, manifest (LAST), rename
        model_bytes = model_text.encode()
        state_bytes = pickle.dumps(global_state, protocol=4)
        atomic_write_bytes(os.path.join(stage, MODEL_NAME), model_bytes)
        atomic_write_bytes(os.path.join(stage, STATE_NAME), state_bytes)
        faults.maybe_kill_in_ckpt_write(self._fault_plan, iteration)
        partition = {
            "world_size": world,
            "global_rows": int(global_rows),
            "ranks": [{"rank": m["rank"],
                       "row_start": m["row_start"],
                       "row_count": m["row_count"],
                       "label_sha256": m["label_sha256"],
                       "valid_counts": m["valid_counts"]}
                      for m in sorted(metas, key=lambda m: m["rank"])],
        }
        part_bytes = json.dumps(partition, indent=1, sort_keys=True).encode()
        atomic_write_bytes(os.path.join(stage, PARTITION_NAME), part_bytes)
        files = {
            MODEL_NAME: {"bytes": len(model_bytes),
                         "sha256": hashlib.sha256(model_bytes).hexdigest()},
            STATE_NAME: {"bytes": len(state_bytes),
                         "sha256": hashlib.sha256(state_bytes).hexdigest()},
            PARTITION_NAME: {"bytes": len(part_bytes),
                             "sha256": hashlib.sha256(part_bytes).hexdigest()},
        }
        for m in metas:
            files[shard_name(m["rank"])] = {"bytes": m["bytes"],
                                            "sha256": m["sha256"]}
        manifest = {
            "format": MANIFEST_FORMAT,
            "iteration": int(iteration),
            "params_hash": phash,
            "world_size": world,
            # per-RANK dataset fingerprints: each rank's local rows are a
            # different dataset slice, so one scalar cannot identify them
            "dataset_fingerprint": {str(m["rank"]): m["fingerprint"]
                                    for m in metas},
            "files": files,
            "health": distributed.health_snapshot(),
        }
        atomic_write_text(os.path.join(stage, MANIFEST_NAME),
                          json.dumps(manifest, indent=1, sort_keys=True))
        os.replace(stage, path)
        for m in metas:
            faults.maybe_corrupt_shard(
                self._fault_plan, os.path.join(path, shard_name(m["rank"])),
                m["rank"])
        faults.maybe_corrupt_checkpoint(self._fault_plan,
                                        os.path.join(path, MODEL_NAME))
        self._prune()
        return path

    def _write(self, booster, iteration: int) -> str:
        """Stage the whole checkpoint in ``ckpt_N.tmp`` and publish it with
        one directory rename. A writer killed at ANY point leaves either no
        ``ckpt_N`` at all (a stale ``.tmp`` the name filter ignores and the
        next write cleans) or a complete one — and within the stage the
        manifest still lands last, so even a non-staged legacy directory
        can only be complete-or-rejected."""
        name = f"ckpt_{iteration:08d}"
        path = os.path.join(self.directory, name)
        stage = path + ".tmp"
        os.makedirs(self.directory, exist_ok=True)
        self._clean_stale_tmp()
        if os.path.isdir(path):
            if self._quick_valid(path):
                # a resumed incarnation re-reaches an already-checkpointed
                # iteration: resume is bit-identical, so the existing
                # VALID checkpoint already holds these bytes — keeping it
                # (instead of delete-then-republish) means a kill can
                # never destroy a published valid checkpoint
                self._prune()
                return path
            shutil.rmtree(path, ignore_errors=True)
        os.makedirs(stage, exist_ok=True)
        model_bytes = booster.model_to_string(num_iteration=-1).encode()
        state_bytes = pickle.dumps(capture_state(booster), protocol=4)
        atomic_write_bytes(os.path.join(stage, MODEL_NAME), model_bytes)
        atomic_write_bytes(os.path.join(stage, STATE_NAME), state_bytes)
        faults.maybe_kill_in_ckpt_write(self._fault_plan, iteration)
        if self._dataset_fp is None:
            self._dataset_fp = dataset_fingerprint(
                booster._boosting.train_set)
        phash = getattr(booster, "_initial_params_hash", None) \
            or params_hash(booster.config)
        from . import distributed
        manifest = {
            "format": MANIFEST_FORMAT,
            "iteration": int(iteration),
            "params_hash": phash,
            "dataset_fingerprint": self._dataset_fp,
            "files": {
                MODEL_NAME: {"bytes": len(model_bytes),
                             "sha256": hashlib.sha256(model_bytes).hexdigest()},
                STATE_NAME: {"bytes": len(state_bytes),
                             "sha256": hashlib.sha256(state_bytes).hexdigest()},
            },
            # supervision telemetry: which incarnation wrote this, and the
            # gang's liveness view at write time (postmortem breadcrumbs)
            "health": distributed.health_snapshot(),
        }
        # the manifest lands LAST within the stage; the rename publishes
        # the complete checkpoint atomically (the target cannot exist:
        # valid ones short-circuited above, invalid ones were removed)
        atomic_write_text(os.path.join(stage, MANIFEST_NAME),
                          json.dumps(manifest, indent=1, sort_keys=True))
        os.replace(stage, path)
        faults.maybe_corrupt_checkpoint(self._fault_plan,
                                        os.path.join(path, MODEL_NAME))
        self._prune()
        return path

    def _clean_stale_tmp(self) -> None:
        """Remove ``ckpt_*.tmp`` staging directories a killed writer left
        behind (they never match ``_CKPT_RE`` so readers already ignore
        them; this reclaims the disk)."""
        try:
            entries = os.listdir(self.directory)
        except OSError:
            return
        for entry in entries:
            if entry.startswith("ckpt_") and entry.endswith(".tmp"):
                stale = os.path.join(self.directory, entry)
                log.warning(f"removing stale checkpoint staging dir "
                            f"{entry} (writer was killed mid-write)")
                shutil.rmtree(stale, ignore_errors=True)

    def _quick_valid(self, path: str) -> bool:
        """Cheap structural validation for PRUNING decisions: manifest
        parses and every listed file exists with the recorded byte length.
        (Checksums are deliberately skipped — pruning runs on every save;
        ``validate`` does the full sha256 pass on the read side.)"""
        mpath = os.path.join(path, MANIFEST_NAME)
        try:
            with open(mpath) as fh:
                manifest = json.load(fh)
            if manifest.get("format") != MANIFEST_FORMAT:
                return False
            files = manifest.get("files", {})
            if not files:
                return False
            for fname, meta in files.items():
                if os.path.getsize(os.path.join(path, fname)) \
                        != int(meta["bytes"]):
                    return False
        except (OSError, ValueError, KeyError, TypeError):
            return False
        return True

    def _prune(self) -> None:
        """Retention by VALIDITY, not by name: keep the newest ``keep``
        structurally valid checkpoints; checkpoints that fail validation
        are deleted (they can never be resumed from) and never count
        toward ``keep`` — so a run of damaged newer checkpoints can't
        evict the newest checkpoint that actually works."""
        valid, invalid = [], []
        for it, path in self.checkpoints():
            (valid if self._quick_valid(path) else invalid).append(
                (it, path))
        for it, path in valid[:-self.keep]:
            shutil.rmtree(path, ignore_errors=True)
        for it, path in invalid:
            log.warning(f"pruning invalid checkpoint "
                        f"{os.path.basename(path)} (failed structural "
                        f"validation; it could never be resumed from)")
            shutil.rmtree(path, ignore_errors=True)

    # -------------------------------------------------------------- read
    def checkpoints(self) -> List[Tuple[int, str]]:
        """(iteration, path) pairs sorted ascending by iteration."""
        out = []
        try:
            entries = os.listdir(self.directory)
        except OSError:
            return []
        for entry in entries:
            m = _CKPT_RE.match(entry)
            if m:
                out.append((int(m.group(1)),
                            os.path.join(self.directory, entry)))
        return sorted(out)

    def validate(self, path: str) -> Dict[str, Any]:
        """Parse + integrity-check one checkpoint's manifest; raises
        ValueError naming what failed."""
        mpath = os.path.join(path, MANIFEST_NAME)
        if not os.path.exists(mpath):
            raise ValueError("no manifest (checkpoint write did not complete)")
        try:
            with open(mpath) as fh:
                manifest = json.load(fh)
        except (OSError, ValueError) as e:
            raise ValueError(f"unreadable manifest: {e}")
        if manifest.get("format") != MANIFEST_FORMAT:
            raise ValueError(f"unknown manifest format "
                             f"{manifest.get('format')!r}")
        for fname, meta in manifest.get("files", {}).items():
            fpath = os.path.join(path, fname)
            if not os.path.exists(fpath):
                raise ValueError(f"missing file {fname}")
            size = os.path.getsize(fpath)
            if size != int(meta["bytes"]):
                raise ValueError(f"{fname} is {size} bytes, manifest says "
                                 f"{meta['bytes']} (truncated?)")
            with open(fpath, "rb") as fh:
                digest = hashlib.sha256(fh.read()).hexdigest()
            if digest != meta["sha256"]:
                raise ValueError(f"{fname} checksum mismatch (corrupt)")
        return manifest

    def load_latest_valid(self) -> Optional[LoadedCheckpoint]:
        """Newest checkpoint that passes integrity validation, falling back
        past truncated/corrupt ones with a warning; None when the
        directory holds no valid checkpoint. Sharded checkpoints (manifest
        lists shard files) also parse PARTITION.json — integrity of every
        shard was already part of ``validate``, so a checkpoint missing a
        shard (or with a shard checksum mismatch) falls back here exactly
        like a truncated replicated one."""
        for iteration, path in reversed(self.checkpoints()):
            try:
                manifest = self.validate(path)
                with open(os.path.join(path, MODEL_NAME), encoding="utf-8") as fh:
                    model_text = fh.read()
                with open(os.path.join(path, STATE_NAME), "rb") as fh:
                    state = pickle.load(fh)
                partition = None
                if PARTITION_NAME in manifest.get("files", {}):
                    with open(os.path.join(path, PARTITION_NAME)) as fh:
                        partition = json.load(fh)
            except (ValueError, OSError, pickle.UnpicklingError, EOFError,
                    TypeError) as e:
                # TypeError covers structurally-incompatible pickles: a
                # namedtuple in the state (e.g. GrowAux) that gained a
                # field since the checkpoint was written unpickles via
                # cls(*old_fields) and raises TypeError — treat it like
                # corruption and fall back rather than crash the resume
                log.warning(f"checkpoint {os.path.basename(path)} is corrupt "
                            f"or truncated ({e}); falling back to the "
                            f"previous checkpoint")
                continue
            return LoadedCheckpoint(path=path, iteration=iteration,
                                    manifest=manifest, model_text=model_text,
                                    state=state, partition=partition)
        return None


def load_shard(ckpt_path: str, rank: int) -> Dict[str, Any]:
    """Unpickle one rank's score-cache shard of a sharded checkpoint
    (integrity against the manifest was already checked by ``validate``)."""
    with open(os.path.join(ckpt_path, shard_name(rank)), "rb") as fh:
        return pickle.load(fh)


def _cumulative_ranges(counts: List[int]) -> List[Tuple[int, int]]:
    out, start = [], 0
    for c in counts:
        out.append((start, int(c)))
        start += int(c)
    return out


def reassemble_local_state(ckpt: LoadedCheckpoint, row_start: int,
                           row_count: int,
                           valid_ranges: List[Tuple[int, int]]) -> Dict[str, Any]:
    """Rebuild THIS rank's local trainer state (train/valid score caches)
    from a sharded checkpoint written under any world size: each requested
    row range is reassembled from the overlapping old shards
    (``distributed.repartition_rows``), touching only the shard files that
    overlap — a same-partition resume reads exactly its own shard."""
    from . import distributed
    part = ckpt.partition or {}
    ranks = part.get("ranks") or []
    old_train = [(e["row_start"], e["row_count"]) for e in ranks]
    cache: Dict[int, Dict[str, Any]] = {}

    def fetch(field, vi=None):
        def _fetch(r):
            import numpy as np
            if r not in cache:
                cache[r] = load_shard(ckpt.path, r)
            s = cache[r]
            return np.asarray(s[field] if vi is None
                              else s["valid_scores"][vi])
        return _fetch

    train_score = distributed.repartition_rows(
        old_train, row_start, row_count, fetch("train_score"))
    valid_scores = []
    for vi, (vs, vc) in enumerate(valid_ranges):
        old_valid = _cumulative_ranges(
            [e["valid_counts"][vi] for e in ranks])
        valid_scores.append(distributed.repartition_rows(
            old_valid, vs, vc, fetch(None, vi)))
    return {"train_score": train_score, "valid_scores": valid_scores}


def _validate_sharded_dataset(booster, ckpt: LoadedCheckpoint,
                              row_start: int, row_count: int) -> None:
    """Dataset-identity checks for a sharded resume. Same-partition ranks
    compare their per-rank fingerprint exactly; after a re-partition the
    new rank instead recomputes the recorded per-old-rank label hashes for
    every old range its new range fully contains — pure row content, so it
    works at any world size."""
    part = ckpt.partition or {}
    ts = booster._boosting.train_set
    global_rows = int(getattr(ts, "num_data", 0) or 0)
    if int(part.get("global_rows", -1)) != global_rows:
        log.fatal(
            f"cannot resume from {ckpt.path}: it was written for "
            f"{part.get('global_rows')} global rows, this dataset has "
            f"{global_rows}.")
    want_fp = ckpt.manifest.get("dataset_fingerprint")
    ranks = part.get("ranks") or []
    exact = next((e for e in ranks
                  if int(e["row_start"]) == row_start
                  and int(e["row_count"]) == row_count), None)
    if exact is not None and isinstance(want_fp, dict):
        rec = want_fp.get(str(exact["rank"]))
        fp = dataset_fingerprint(ts, local=True)
        if rec and rec != fp:
            log.fatal(
                f"cannot resume from {ckpt.path}: it was written against "
                f"a different training dataset (rank {exact['rank']} "
                f"fingerprint {rec} != {fp}).")
    label = ts.get_label() if hasattr(ts, "get_label") else None
    if label is None:
        return
    lo, hi = row_start, row_start + row_count
    for e in ranks:
        s, c = int(e["row_start"]), int(e["row_count"])
        if s >= lo and s + c <= hi and e.get("label_sha256"):
            got = label_range_sha256(label, s - lo, s + c - lo)
            if got != e["label_sha256"]:
                log.fatal(
                    f"cannot resume from {ckpt.path}: label rows "
                    f"[{s}, {s + c}) do not match the checkpoint's "
                    f"recorded content hash — the dataset changed (or "
                    f"rows were reordered) since the checkpoint was "
                    f"written.")


def restore_booster(booster, ckpt: LoadedCheckpoint) -> Dict[str, Any]:
    """Restore a freshly constructed training booster to the checkpointed
    state after validating that params and dataset match what the
    checkpoint was written with. Sharded checkpoints additionally
    reassemble this rank's score caches from the shard files under the
    CURRENT partition (resume at a different world size re-partitions on
    load). Returns the saved callback states (keyed by ``ckpt_key``) for
    the engine to hand to its callbacks."""
    phash = getattr(booster, "_initial_params_hash", None) \
        or params_hash(booster.config)
    want = ckpt.manifest.get("params_hash")
    if want and want != phash:
        log.fatal(
            f"cannot resume from {ckpt.path}: it was written with different "
            f"training parameters (params_hash {want} != {phash}) — "
            f"resuming would silently train a different model. Use the "
            f"original parameters, or delete the checkpoint directory to "
            f"start fresh.")
    boosting = booster._boosting
    if ckpt.partition is not None:
        from . import distributed
        ts = boosting.train_set
        row_start = int(getattr(ts, "local_row_start", 0) or 0)
        n_local = getattr(ts, "num_local_data", None)
        row_count = int(n_local if n_local is not None else ts.num_data)
        _validate_sharded_dataset(booster, ckpt, row_start, row_count)
        my_valid_counts = [int(s.shape[0]) for s in boosting._valid_scores]
        ranks = ckpt.partition.get("ranks") or []
        old_nvalid = len(ranks[0].get("valid_counts") or []) if ranks else 0
        if len(my_valid_counts) != old_nvalid:
            log.fatal(
                f"cannot resume from {ckpt.path}: it was written with "
                f"{old_nvalid} validation sets; this run has "
                f"{len(my_valid_counts)} — pass the same valid_sets in the "
                f"same order")
        if getattr(boosting, "_pre_part", False):
            # each new rank's valid-row offsets come from the counts of
            # the ranks below it (coordination-service exchange; trivial
            # at W=1)
            import jax
            all_counts = [json.loads(p) for p in distributed.exchange_host(
                "resume_valid_counts", json.dumps(my_valid_counts))]
            me = jax.process_index()
            valid_ranges = [
                (sum(c[vi] for c in all_counts[:me]), my_valid_counts[vi])
                for vi in range(len(my_valid_counts))]
        else:
            # REPLICATED booster reading a sharded checkpoint: every rank
            # holds the FULL row set, so every range starts at 0 (no
            # exchange — all ranks skip it consistently)
            valid_ranges = [(0, c) for c in my_valid_counts]
            if getattr(boosting, "_need_bagging", False):
                log.warning(
                    "resuming a pre-partitioned (sharded) checkpoint with "
                    "replicated data: the bagging sample stream is "
                    "mode-dependent (pre-partitioned draws are keyed per "
                    "global row), so continued training will not "
                    "bit-match a continuation of the original "
                    "pre-partitioned run")
        local = reassemble_local_state(ckpt, row_start, row_count,
                                       valid_ranges)
        merged = dict(ckpt.state["boosting"])
        merged.update(local)
        boosting.set_trainer_state(merged)
    else:
        import jax
        if getattr(boosting, "_pre_part", False) and jax.process_count() > 1:
            log.fatal(
                f"cannot resume from {ckpt.path}: the checkpoint is not "
                f"sharded (no {PARTITION_NAME}), but this is a "
                f"multi-process pre-partitioned run whose score caches "
                f"are process-local. Re-run the original training with "
                f"checkpoint_shards=true, or resume replicated.")
        fp = dataset_fingerprint(boosting.train_set)
        want_fp = ckpt.manifest.get("dataset_fingerprint")
        if want_fp and not isinstance(want_fp, dict) and want_fp != fp:
            log.fatal(
                f"cannot resume from {ckpt.path}: it was written against a "
                f"different training dataset (fingerprint {want_fp} != "
                f"{fp}).")
        boosting.set_trainer_state(ckpt.state["boosting"])
    b = ckpt.state.get("booster", {})
    booster.best_iteration = b.get("best_iteration", -1)
    booster.best_score = dict(b.get("best_score", {}))
    if b.get("attr"):
        booster._attr = dict(b["attr"])
    return dict(ckpt.state.get("callbacks", {}))


def _near_equal_counts(total: int, parts: int) -> List[int]:
    base, rem = divmod(int(total), int(parts))
    return [base + (1 if r < rem else 0) for r in range(parts)]


def repartition_checkpoint(ckpt_path: str, new_world_size: int,
                           dest_dir: str) -> str:
    """Offline re-shard: rewrite a SHARDED checkpoint for a different
    world size (near-equal contiguous row ranges) into ``dest_dir`` —
    what an operator runs before relaunching a pre-partitioned gang on a
    different machine count when they prefer the re-shard cost paid once,
    offline, instead of at load (the resume path re-partitions on load by
    itself either way; tests also use this to fabricate any-world
    checkpoints). Pure row movement — every row's f32 score bits are
    preserved exactly. Returns the new checkpoint path."""
    import numpy as np
    ckpt_path = os.path.abspath(ckpt_path)
    new_world_size = int(new_world_size)
    if new_world_size < 1:
        raise ValueError(f"new_world_size must be >= 1, got {new_world_size}")
    src_mgr = CheckpointManager(os.path.dirname(ckpt_path))
    manifest = src_mgr.validate(ckpt_path)
    if PARTITION_NAME not in manifest.get("files", {}):
        raise ValueError(f"{ckpt_path} is not a sharded checkpoint "
                         f"(no {PARTITION_NAME})")
    with open(os.path.join(ckpt_path, PARTITION_NAME)) as fh:
        partition = json.load(fh)
    ranks = partition["ranks"]
    shards = [load_shard(ckpt_path, e["rank"]) for e in ranks]
    train = np.concatenate([np.asarray(s["train_score"]) for s in shards],
                           axis=0)
    nvalid = len(ranks[0].get("valid_counts") or []) if ranks else 0
    valids = [np.concatenate([np.asarray(s["valid_scores"][vi])
                              for s in shards], axis=0)
              for vi in range(nvalid)]
    counts = _near_equal_counts(partition["global_rows"], new_world_size)
    vcounts = [_near_equal_counts(v.shape[0], new_world_size)
               for v in valids]
    old_by_range = {(int(e["row_start"]), int(e["row_count"])): e
                    for e in ranks}
    iteration = int(manifest["iteration"])
    name = f"ckpt_{iteration:08d}"
    os.makedirs(dest_dir, exist_ok=True)
    dest = os.path.join(dest_dir, name)
    stage = dest + ".tmp"
    if os.path.isdir(stage):
        shutil.rmtree(stage)
    os.makedirs(stage)
    files = {}
    new_ranks = []
    start = 0
    vstarts = [0] * nvalid
    for r, count in enumerate(counts):
        local = {
            "train_score": train[start:start + count],
            "valid_scores": [valids[vi][vstarts[vi]:vstarts[vi]
                                        + vcounts[vi][r]]
                             for vi in range(nvalid)],
        }
        shard_bytes = pickle.dumps(local, protocol=4)
        atomic_write_bytes(os.path.join(stage, shard_name(r)), shard_bytes)
        files[shard_name(r)] = {
            "bytes": len(shard_bytes),
            "sha256": hashlib.sha256(shard_bytes).hexdigest()}
        # content hashes / fingerprints are only carried over for ranges
        # that map EXACTLY onto an old rank (labels are not stored in the
        # checkpoint, so they cannot be recomputed offline)
        old = old_by_range.get((start, count))
        new_ranks.append({
            "rank": r, "row_start": start, "row_count": count,
            "label_sha256": old.get("label_sha256") if old else None,
            "valid_counts": [vcounts[vi][r] for vi in range(nvalid)]})
        start += count
        for vi in range(nvalid):
            vstarts[vi] += vcounts[vi][r]
    for fname in (MODEL_NAME, STATE_NAME):
        shutil.copy2(os.path.join(ckpt_path, fname),
                     os.path.join(stage, fname))
        files[fname] = dict(manifest["files"][fname])
    new_partition = {"world_size": new_world_size,
                     "global_rows": int(partition["global_rows"]),
                     "ranks": new_ranks}
    part_bytes = json.dumps(new_partition, indent=1, sort_keys=True).encode()
    atomic_write_bytes(os.path.join(stage, PARTITION_NAME), part_bytes)
    files[PARTITION_NAME] = {
        "bytes": len(part_bytes),
        "sha256": hashlib.sha256(part_bytes).hexdigest()}
    old_fp = manifest.get("dataset_fingerprint")
    new_fp = {}
    if isinstance(old_fp, dict):
        for e in new_ranks:
            old = old_by_range.get((e["row_start"], e["row_count"]))
            if old is not None and str(old["rank"]) in old_fp:
                new_fp[str(e["rank"])] = old_fp[str(old["rank"])]
    new_manifest = dict(manifest)
    new_manifest.update({"world_size": new_world_size,
                         "dataset_fingerprint": new_fp, "files": files})
    atomic_write_text(os.path.join(stage, MANIFEST_NAME),
                      json.dumps(new_manifest, indent=1, sort_keys=True))
    if os.path.isdir(dest):
        shutil.rmtree(dest)
    os.replace(stage, dest)
    return dest

"""tree_learner dispatch: serial / data / feature / voting over a device mesh.

The analog of the reference's TreeLearner factory
(reference: include/LightGBM/tree_learner.h:104 ``CreateTreeLearner``:
(serial|feature|data|voting) x device). Here every distributed mode is the
SAME jitted grower (models/grower.py) under a ``shard_map`` with a
mode-specific sharding layout and collective pattern:

- ``data``: rows sharded; histogram tiles ``psum_scatter``'d over feature
  ownership, owner search, best-split allreduce-argmax (reference:
  data_parallel_tree_learner.cpp:184-186 ReduceScatter + HistogramSumReducer,
  parallel_tree_learner.h:191 SyncUpGlobalBestSplit).
- ``feature``: rows replicated, features sliced; no histogram communication,
  only the best-split sync (reference:
  feature_parallel_tree_learner.cpp:59-78).
- ``voting``: rows sharded; local top-k vote elects 2k features per leaf and
  only those columns are summed (reference:
  voting_parallel_tree_learner.cpp:151-182 GlobalVoting).

The mesh is a 1-D enumeration of the visible devices (multi-host: initialize
``jax.distributed`` before constructing the Booster and every process sees
the global mesh).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from ..ops.split import FeatureMeta
from ..models.grower import GrowAux, grow_tree
from .data_parallel import make_mesh

PARALLEL_MODES = ("data", "feature", "voting")


def _shard_map(fn, *, mesh, in_specs, out_specs):
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def _pad_cols(b, *, f_pad):
    return jnp.pad(b, ((0, 0), (0, f_pad)))


def _pad_rows(n_pad, *arrays):
    out = []
    for a in arrays:
        if a is None:
            out.append(None)
        elif a.ndim == 1:
            out.append(jnp.pad(a, (0, n_pad)))
        else:
            out.append(jnp.pad(a, ((0, n_pad), (0, 0))))
    return out


def pad_bundle_meta(bundle_meta, f_pad: int):
    """Pad EFB bundle metadata with inert (non-bundle) columns whose single
    segment spans the full bin range — the grower slices bundle rows by the
    PADDED feature offset, so misaligned rows would corrupt real columns."""
    b = bundle_meta.seg_lo.shape[1]
    return type(bundle_meta)(
        seg_lo=jnp.pad(bundle_meta.seg_lo, ((0, f_pad), (0, 0))),
        seg_hi=jnp.pad(bundle_meta.seg_hi, ((0, f_pad), (0, 0)),
                       constant_values=b - 1),
        is_bundle=jnp.pad(bundle_meta.is_bundle, (0, f_pad)),
        fwd_ok=jnp.pad(bundle_meta.fwd_ok, ((0, f_pad), (0, 0))),
        rev_ok=jnp.pad(bundle_meta.rev_ok, ((0, f_pad), (0, 0))),
        # padded columns never produce valid candidates; preference 0
        # keeps them below every real candidate
        pref_fwd=jnp.pad(bundle_meta.pref_fwd, ((0, f_pad), (0, 0))),
        pref_rev=jnp.pad(bundle_meta.pref_rev, ((0, f_pad), (0, 0))))


def _pad_features(meta: FeatureMeta, f_pad: int) -> FeatureMeta:
    """Pad per-feature metadata with inert features (2 bins, no missing,
    numerical, unconstrained) — they are masked off via feature_mask."""
    return FeatureMeta(
        num_bins=jnp.pad(meta.num_bins, (0, f_pad), constant_values=2),
        missing_type=jnp.pad(meta.missing_type, (0, f_pad)),
        default_bin=jnp.pad(meta.default_bin, (0, f_pad)),
        is_categorical=jnp.pad(meta.is_categorical, (0, f_pad)),
        monotone=jnp.pad(meta.monotone, (0, f_pad)),
        penalty=jnp.pad(meta.penalty, (0, f_pad), constant_values=1.0),
    )


class ParallelGrower:
    """Caches one shard_map'd grower per static configuration so repeated
    boosting iterations reuse the compiled program (the reference constructs
    its tree learner once in GBDT::Init, gbdt.cpp:49-138)."""

    def __init__(self, mode: str, mesh: Optional[Mesh] = None,
                 axis: str = "shard"):
        assert mode in PARALLEL_MODES, mode
        self.mode = mode
        self.axis = axis
        self.mesh = mesh if mesh is not None else make_mesh(axis=axis)
        self.ndev = self.mesh.shape[axis]
        self._cache = {}
        self._global_arrays = {}   # id(host arr) -> (host arr, global arr)

    def _build(self, extras_spec: dict, grow_kwargs: tuple,
               pre_part: bool = False):
        axis = self.axis
        kw = dict(grow_kwargs)
        if self.mode == "data":
            kw.update(axis_name=axis, feature_axis_name=axis,
                      feature_shards=self.ndev)
        elif self.mode == "feature":
            kw.update(feature_axis_name=axis, feature_shards=self.ndev)
        else:  # voting
            kw.update(axis_name=axis, voting=True)

        rows_sharded = self.mode in ("data", "voting")
        row = P(axis) if rows_sharded else P()
        row2 = P(axis, None) if rows_sharded else P()
        # replicated-data multi-controller (every process constructed the
        # full Dataset): replicate the leaf ids with an in-program
        # all_gather so every process can address the full vector for its
        # full-length score update. Pre-partitioned mode keeps leaf_id
        # ROW-SHARDED end to end — the score update consumes only the
        # process-local shard (the reference's per-machine score partition,
        # score_updater.hpp), so no O(N_global) array ever lands on a host
        multiproc = jax.process_count() > 1
        gather_leaf = multiproc and rows_sharded and not pre_part

        def fn(bins, grad, hess, mask, meta, params, fmask, missing_bin,
               extras, rng_key):
            tree, leaf_id, aux = grow_tree(
                bins, grad, hess, mask, meta, params, fmask, missing_bin,
                binsT=extras.get("binsT"),
                bundle_meta=extras.get("bundle"),
                forced_splits=extras.get("forced"),
                rng_key=rng_key, **kw)
            if gather_leaf:
                with jax.named_scope("score_update"):
                    leaf_id = jax.lax.all_gather(leaf_id, axis, tiled=True)
            return tree, leaf_id, aux

        leaf_spec = P() if gather_leaf else row
        in_specs = (row2, row, row, row, P(), P(), P(), P(), extras_spec,
                    P())
        out_specs = (P(), leaf_spec, GrowAux(*(P(),) * 7))
        # jit the shard_map: a BARE shard_map re-traces and re-compiles on
        # every invocation, which made each unfused parallel-learner
        # iteration (the only path pre-partitioned runs have) pay a full
        # grower compile (~60 XLA compiles/iter measured on CPU). The
        # fused path embeds this same fn inside its own jit, where the
        # extra jit wrapper simply inlines.
        return jax.jit(_shard_map(fn, mesh=self.mesh, in_specs=in_specs,
                                  out_specs=out_specs))

    def pad_replicated_inputs(self, bins, binsT, meta, missing_bin,
                              bundle_meta):
        """Pad the dataset-constant arrays of the replicated (single-
        controller) path to mesh-divisible shapes — the ONE definition of
        the row/feature padding rules, shared by the per-call unfused
        ``__call__`` below and the fused step's build-once bindings
        (models/gbdt.py _fused_parallel_bindings), so the two paths
        cannot drift. Returns ``(bins, binsT, meta, missing_bin,
        bundle_meta, n_pad, f_pad)``."""
        n, f = bins.shape
        d = self.ndev
        n_pad = (-n) % d if self.mode in ("data", "voting") else 0
        f_pad = (-f) % d if self.mode in ("data", "feature") else 0
        if n_pad:
            bins = jnp.pad(bins, ((0, n_pad), (0, 0)))
            if binsT is not None:
                binsT = jnp.pad(binsT, ((0, 0), (0, n_pad)))
        if f_pad:
            bins = jnp.pad(bins, ((0, 0), (0, f_pad)))
            if binsT is not None:
                binsT = jnp.pad(binsT, ((0, f_pad), (0, 0)))
        meta, missing_bin, bundle_meta = self._pad_feature_tables(
            meta, missing_bin, bundle_meta, f_pad)
        return bins, binsT, meta, missing_bin, bundle_meta, n_pad, f_pad

    @staticmethod
    def _pad_feature_tables(meta, missing_bin, bundle_meta, f_pad: int):
        """The per-feature tables beside ``f_pad`` inert columns."""
        if f_pad:
            meta = _pad_features(meta, f_pad)
            missing_bin = jnp.pad(missing_bin, (0, f_pad),
                                  constant_values=-1)
            if bundle_meta is not None:
                bundle_meta = pad_bundle_meta(bundle_meta, f_pad)
        return meta, missing_bin, bundle_meta

    def takes_row_shards(self, row_bins) -> bool:
        """Whether ``row_bins`` (``Dataset.row_bins``) already lies as
        this learner shards rows: over the same devices, in mesh order."""
        return (row_bins is not None and self.mode in ("data", "voting")
                and getattr(row_bins.sharding, "mesh", None) == self.mesh)

    def pad_row_sharded_inputs(self, row_bins, n: int, want_binsT: bool,
                               meta, missing_bin, bundle_meta):
        """``pad_replicated_inputs`` for a bin matrix that is row-sharded
        already (its rows past ``n`` are the row padding): the inert
        columns are appended and the feature-major copy is transposed
        shard by shard, each on its own device, so no device ever holds
        more than its rows. Same return."""
        f_pad = (-row_bins.shape[1]) % self.ndev if self.mode == "data" \
            else 0
        sharded = functools.partial(jax.sharding.NamedSharding, self.mesh)
        bins = row_bins
        if f_pad:
            bins = jax.jit(functools.partial(_pad_cols, f_pad=f_pad),
                           out_shardings=sharded(P(self.axis, None)))(bins)
        binsT = jax.jit(lambda b: b.T, out_shardings=sharded(
            P(None, self.axis)))(bins) if want_binsT else None
        meta, missing_bin, bundle_meta = self._pad_feature_tables(
            meta, missing_bin, bundle_meta, f_pad)
        return (bins, binsT, meta, missing_bin, bundle_meta,
                row_bins.shape[0] - n, f_pad)

    def build_extras(self, binsT, bundle_meta, forced_splits):
        """Assemble the optional-operand dict + its PartitionSpecs for
        the shard fn (the single definition of the binsT/bundle/forced
        wiring both call paths share)."""
        extras, extras_spec = {}, {}
        rows_sharded = self.mode in ("data", "voting")
        if binsT is not None:
            extras["binsT"] = binsT
            extras_spec["binsT"] = (P(None, self.axis) if rows_sharded
                                    else P())
        if bundle_meta is not None:
            extras["bundle"] = bundle_meta
            extras_spec["bundle"] = type(bundle_meta)(
                *(P() for _ in bundle_meta))
        if forced_splits is not None:
            extras["forced"] = forced_splits
            extras_spec["forced"] = tuple(P() for _ in forced_splits)
        return extras, extras_spec

    def get_shard_fn(self, extras_spec: dict, grow_kwargs: tuple,
                     pre_part: bool = False):
        """The cached shard_map'd grower for a static configuration — the
        single compile cache BOTH call paths share: the unfused per-phase
        ``__call__`` below and the fused one-dispatch iteration
        (models/gbdt.py ``_fused_step_fn``) embed the same program, so a
        config admitted to the fused path never compiles the grower
        twice."""
        key = (("prepart",) if pre_part else ()) + (
            frozenset(extras_spec), grow_kwargs)
        shard = self._cache.get(key)
        if shard is None:
            shard = self._build(extras_spec, grow_kwargs,
                                pre_part=pre_part)
            self._cache[key] = shard
        return shard

    def _to_global(self, arr, spec, key=None):
        """Put an operand where the shard fn's in_specs say it lives: a
        mesh-wide array laid out by ``spec``. Left on the default device
        it would still run — jit moves it — but every call would then
        ship the whole array from the first device to the others, and
        that device would hold all of it. Multi-controller: every process
        constructed the same Dataset (the reference's machine-list flow
        where each machine loads data and the learner operates on its row
        shard) and materializes only its addressable shards. ``key`` (the
        pre-padding original of a dataset-constant input) caches the
        placement so bins/meta/masks move once, not once per tree."""
        if arr is None:
            return arr

        def build():
            sharding = jax.sharding.NamedSharding(self.mesh, spec)
            try:
                # device_put reshards without a host round trip when the
                # input is already device-resident (the grad/hess path)
                return jax.device_put(arr, sharding)
            except Exception:
                host = np.asarray(arr)
                return jax.make_array_from_callback(host.shape, sharding,
                                                    lambda idx: host[idx])

        return build() if key is None else self._cached_global(key, build)

    def place_constants(self, bins, meta, missing_bin, extras, extras_spec,
                        keys=None):
        """The dataset-constant operands of the shard fn, each placed by
        the spec ``_build`` gives it (rows of the bin matrices over the
        mesh axis for the data/voting learners, everything else
        replicated). ``keys``: matching pre-padding originals to cache the
        placement under, for callers that pad per call."""
        rows_sharded = self.mode in ("data", "voting")
        k = keys or {}

        def replicate(tree, name):
            # every leaf replicated, cached under the matching leaf of
            # the caller's original where there is one
            return jax.tree.map(
                lambda a, ka: self._to_global(
                    a, P(), key=ka if name in k else None),
                tree, k.get(name, tree))

        bins = self._to_global(bins, P(self.axis, None) if rows_sharded
                               else P(), key=k.get("bins"))
        meta = replicate(meta, "meta")
        missing_bin = replicate(missing_bin, "missing_bin")
        extras = dict(extras)
        if "binsT" in extras:
            extras["binsT"] = self._to_global(
                extras["binsT"], extras_spec["binsT"], key=k.get("binsT"))
        for name in ("bundle", "forced"):
            if name in extras:
                extras[name] = replicate(extras[name], name)
        return bins, meta, missing_bin, extras

    def place_rows(self, tree, n: int):
        """The per-row operands of the fused step (the leaves of ``tree``
        whose leading axis is the ``n`` score rows: the score, the
        objective's label-sized tables), laid out ONCE as the step reads
        them: over the mesh axis where the rows divide by the mesh, on
        every device where they do not. Left on the default device they
        are uncommitted, and every dispatch slices them anew and ships
        the pieces (two ``_multi_slice`` programs an iteration), after a
        second compile of the step for the layout the first score update
        leaves."""
        if self.mode not in ("data", "voting"):
            return tree
        spec = P(self.axis) if n % self.ndev == 0 else P()
        return jax.tree.map(
            lambda a: self._to_global(a, spec)
            if isinstance(a, jax.Array) and a.ndim and a.shape[0] == n
            else a, tree)

    def _cached_global(self, key, build):
        """id()-keyed LRU over dataset-constant globalized arrays (the
        source object is retained so its id stays unique; bounded so a
        long-lived process over many Datasets doesn't pin old copies)."""
        hit = self._global_arrays.get(id(key))
        if hit is not None and hit[0] is key:
            self._global_arrays.pop(id(key))
            self._global_arrays[id(key)] = hit
            return hit[1]
        out = build()
        if len(self._global_arrays) >= 64:
            self._global_arrays.pop(next(iter(self._global_arrays)))
        self._global_arrays[id(key)] = (key, out)
        return out

    def __call__(self, bins, grad, hess, sample_mask, meta, params,
                 feature_mask, missing_bin, *, binsT=None, rng_key=None,
                 bundle_meta=None, forced_splits=None, pre_part=None,
                 **grow_kwargs):
        n, f = bins.shape
        d = self.ndev
        # pre-partitioned mode (distributed.load_partitioned): bins is
        # already a GLOBAL row-sharded array and grad/hess/mask arrive as
        # this process's LOCAL row slice. Callers holding the Dataset pass
        # the flag explicitly; the addressability probe covers direct
        # multi-process grower-level use (a 1-process pre-partitioned
        # array IS fully addressable, so the flag matters there)
        if pre_part is None:
            pre_part = (isinstance(bins, jax.Array)
                        and not bins.is_fully_addressable)
        if pre_part:
            assert self.mode in ("data", "voting"), (
                "pre-partitioned datasets shard rows; use data/voting")
            assert n % d == 0, (n, d)   # load_partitioned pads rows
            # grad/hess/mask arrive as this process's TRUE local rows; pad
            # to the per-process shard size with zero mass
            loc_target = n // max(jax.process_count(), 1)
            row = P(self.axis)
            sharding = jax.sharding.NamedSharding(self.mesh, row)
            rep = jax.sharding.NamedSharding(self.mesh, P())

            def glob(a, fill=0.0):
                a = np.asarray(a)
                if a.shape[0] < loc_target:
                    a = np.pad(a, (0, loc_target - a.shape[0]),
                               constant_values=fill)
                return jax.make_array_from_process_local_data(sharding, a)

            def glob_rep(a, key=None):
                """Replicate a (process-identical) host array globally."""
                build = lambda: jax.device_put(np.asarray(a), rep)
                return build() if key is None \
                    else self._cached_global(key, build)

            grad = glob(grad)
            hess = glob(hess)
            sample_mask = glob(sample_mask)
            f_pad = (-f) % d if self.mode == "data" else 0
            colT = P(None, self.axis)

            def pad_global(arr, spec, fn):
                """Cached jitted pad of a dataset-constant global array."""
                out_sh = jax.sharding.NamedSharding(self.mesh, spec)
                return self._cached_global(
                    arr, lambda: jax.jit(fn, out_shardings=out_sh)(arr))

            if f_pad:
                meta = _pad_features(meta, f_pad)
                feature_mask = jnp.pad(feature_mask, (0, f_pad))
                missing_bin = jnp.pad(missing_bin, (0, f_pad),
                                      constant_values=-1)
                bins = pad_global(bins, P(self.axis, None),
                                  functools.partial(_pad_cols, f_pad=f_pad))
                if binsT is not None:
                    binsT = pad_global(
                        binsT, colT,
                        lambda b: jnp.pad(b, ((0, f_pad), (0, 0))))
                if bundle_meta is not None:
                    bundle_meta = pad_bundle_meta(bundle_meta, f_pad)
            extras = {}
            extras_spec = {}
            if binsT is not None:
                # already a GLOBAL feature-major array from load_partitioned
                extras["binsT"] = binsT
                extras_spec["binsT"] = colT
            if bundle_meta is not None:
                extras["bundle"] = type(bundle_meta)(
                    *(glob_rep(a, key=ka)
                      for a, ka in zip(bundle_meta, bundle_meta)))
                extras_spec["bundle"] = type(bundle_meta)(
                    *(P() for _ in bundle_meta))
            if forced_splits is not None:
                extras["forced"] = tuple(
                    glob_rep(a, key=ka)
                    for a, ka in zip(forced_splits, forced_splits))
                extras_spec["forced"] = tuple(P() for _ in forced_splits)
            if rng_key is None:
                rng_key = jax.random.PRNGKey(0)
            shard = self.get_shard_fn(extras_spec,
                                      tuple(sorted(grow_kwargs.items())),
                                      pre_part=True)
            tree, leaf_id, aux = shard(bins, grad, hess, sample_mask, meta,
                                       params, feature_mask, missing_bin,
                                       extras, rng_key)
            return tree, leaf_id, aux
        # pre-padding originals key the multi-process globalization cache
        # (padding allocates fresh arrays every call)
        orig_bins, orig_binsT = bins, binsT
        orig_meta, orig_missing_bin = meta, missing_bin
        orig_bundle, orig_forced = bundle_meta, forced_splits
        (bins, binsT, meta, missing_bin, bundle_meta,
         n_pad, f_pad) = self.pad_replicated_inputs(
            bins, binsT, meta, missing_bin, bundle_meta)
        if n_pad:
            _, grad, hess, sample_mask = _pad_rows(n_pad, None, grad, hess,
                                                   sample_mask)
        if f_pad:
            feature_mask = jnp.pad(feature_mask, (0, f_pad))
        if rng_key is None:
            rng_key = jax.random.PRNGKey(0)
        row = P(self.axis) if self.mode in ("data", "voting") else P()
        grad = self._to_global(grad, row)
        hess = self._to_global(hess, row)
        sample_mask = self._to_global(sample_mask, row)
        feature_mask = self._to_global(feature_mask, P())
        extras, extras_spec = self.build_extras(binsT, bundle_meta,
                                                forced_splits)
        bins, meta, missing_bin, extras = self.place_constants(
            bins, meta, missing_bin, extras, extras_spec,
            keys=dict(bins=orig_bins, binsT=orig_binsT, meta=orig_meta,
                      missing_bin=orig_missing_bin, bundle=orig_bundle,
                      forced=orig_forced))

        shard = self.get_shard_fn(extras_spec,
                                  tuple(sorted(grow_kwargs.items())))
        tree, leaf_id, aux = shard(bins, grad, hess, sample_mask, meta,
                                   params, feature_mask, missing_bin,
                                   extras, rng_key)
        return tree, leaf_id[:n], aux

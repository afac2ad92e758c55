"""Dataset: binned training container + metadata.

Mirrors the reference's Python ``Dataset`` API surface
(reference: python-package/lightgbm/basic.py:1195+) on top of the core data
layer (reference: src/io/dataset.cpp Dataset, src/io/metadata.cpp Metadata,
src/io/dataset_loader.cpp DatasetLoader):

- lazy construction (bin mappers fitted on first use, basic.py:1195),
- validation sets aligned to the training set's bin mappers via ``reference``
  (reference: DatasetLoader::LoadFromFileAlignWithOtherDataset,
  dataset_loader.cpp:262-314),
- metadata fields label/weight/group/init_score with ``set_field``/
  ``get_field`` (reference: dataset.h:41-249 Metadata),
- trivial (single-bin) features dropped from the device matrix the way the
  reference drops unused features (``used_feature_map_``, dataset.cpp).

The binned matrix lives device-resident as ``[N, F_used]`` uint8/int32 — the
TPU analog of the reference's FeatureGroup bin storage (dense_bin.hpp), laid
out row-major for row-blocked histogram kernels. EFB bundling IS applied on
the sparse construction path (``_construct_sparse`` -> bundling.py, the
analog of dataset.cpp:239 FastFeatureBundling): mutually-exclusive sparse
features share one dense device column each, so the matrix is ``[N, G]``
with G ~ bundles rather than features; dense float input skips bundling
(every column already owns its device column). High-sparsity columns can
further drop out of the dense matrix entirely into (row, bin) streams
(``_maybe_extract_sparse``, the SparseBin analog).
"""

from __future__ import annotations

from contextlib import contextmanager
from typing import Any, Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from . import binning
from .config import Config
from .ops.split import FeatureMeta
from .utils import log, profiling

@contextmanager
def _stage(seconds: dict, name: str):
    """One host stage of a construct: a span ``lgbm:<name>``, and its
    seconds (the span's own) added to ``seconds[name + "_s"]``."""
    sp = profiling.span(name)
    try:
        with sp:
            yield
    finally:
        seconds[name + "_s"] = seconds.get(name + "_s", 0.0) + sp.seconds


def _to_2d_float(data) -> np.ndarray:
    if hasattr(data, "values"):  # pandas DataFrame/Series
        data = data.values
    if hasattr(data, "toarray"):  # scipy sparse
        data = data.toarray()
    arr = np.asarray(data, dtype=np.float64)
    if arr.ndim == 1:
        arr = arr.reshape(-1, 1)
    return arr


def _is_scipy_sparse(data) -> bool:
    """scipy CSR/CSC/COO — handled without densifying (the reference's
    sparse-input path, c_api.h LGBM_DatasetCreateFromCSR/CSC)."""
    return (hasattr(data, "tocsc") and hasattr(data, "nnz")
            and not hasattr(data, "values"))


def _load_forced_bins(config: Config, num_features: int,
                      categorical: Sequence[int]) -> Dict[int, List[float]]:
    """Forced bin upper bounds from JSON (reference:
    DatasetLoader::GetForcedBins, dataset_loader.cpp:1373-1408; format
    [{"feature": i, "bin_upper_bound": [...]}, ...])."""
    if not config.forcedbins_filename:
        return {}
    import json
    try:
        with open(config.forcedbins_filename) as fh:
            arr = json.load(fh)
    except OSError:
        log.warning(f"Could not open {config.forcedbins_filename}. "
                    f"Will ignore.")
        return {}
    cats = set(int(c) for c in categorical)
    out: Dict[int, List[float]] = {}
    for entry in arr:
        j = int(entry["feature"])
        if j >= num_features:
            log.fatal(f"forced bins feature index {j} out of range")
        if j in cats:
            log.warning(f"Feature {j} is categorical. Will ignore forced "
                        f"bins for this feature.")
            continue
        bounds = [float(v) for v in entry["bin_upper_bound"]]
        deduped = []
        for v in bounds:      # remove consecutive duplicates (reference)
            if not deduped or v != deduped[-1]:
                deduped.append(v)
        out[j] = deduped
    return out


class Dataset:
    """Training/validation data container (reference: basic.py Dataset)."""

    def __init__(self, data, label=None, reference: Optional["Dataset"] = None,
                 weight=None, group=None, init_score=None,
                 feature_name: Union[str, List[str]] = "auto",
                 categorical_feature: Union[str, List[int], List[str]] = "auto",
                 params: Optional[Dict[str, Any]] = None,
                 free_raw_data: bool = True):
        self.data = data
        # chunk-source streaming construction (from_chunks): a re-iterable
        # chunk stream instead of a monolithic matrix
        self._chunk_source = None
        self.label = label
        self.reference = reference
        self.weight = weight
        self.group = group
        self.init_score = init_score
        self.feature_name = feature_name
        self.categorical_feature = categorical_feature
        self.params = dict(params or {})
        self.free_raw_data = free_raw_data
        self._constructed = False
        # populated by construct():
        self.mappers: List[binning.BinMapper] = []
        self.used_features: np.ndarray = np.array([], dtype=np.int32)
        self._bins: Optional[jnp.ndarray] = None      # [N, F_used] device
        # a training set built under a row-sharded learner keeps its bin
        # matrix as one shard a device and no whole copy (see row_bins)
        self._row_bins: Optional[jax.Array] = None
        self.binned_on_device: Optional[bool] = None  # monolithic path
        self.num_data: int = 0
        self.num_total_features: int = 0
        # per-column category lists for pandas category dtypes; raw values
        # are mapped to these codes at train AND predict time (reference:
        # basic.py:504-568 pandas_categorical capture)
        self.pandas_categorical: Dict[int, list] = {}
        # EFB bundles (bundling.py): None = plain per-feature columns
        self.bundles = None
        # sparse device storage (see _maybe_extract_sparse): None = all
        # device columns dense
        self.sp_cols = None
        self.sp_rows = None
        self.sp_cell = None
        self.sp_default = None
        self.sp_offsets = None
        # how this data set was built, for the flight recorder's header and
        # the benchmark: a streaming construct's passes, a sparse
        # construct's bundle and stream counts and its stages' seconds
        self.construct_stats: Optional[Dict[str, Any]] = None

    # ----------------------------------------------------------- storage
    @property
    def bins(self):
        """The whole ``[N, F_used]`` device bin matrix. A row-sharded set
        (``row_bins``) has none until somebody asks: it is then gathered
        through the host onto one device and kept, which costs that device
        what sharding saved. The parallel learners read ``row_bins``."""
        if self._bins is None and self._row_bins is not None:
            log.warning("a row-sharded Dataset is gathered into one whole "
                        "bin matrix on one device (something read "
                        "Dataset.bins)")
            self._bins = jnp.asarray(
                np.asarray(self._row_bins)[:self.num_data])
        return self._bins

    @bins.setter
    def bins(self, value):
        self._bins, self._row_bins = value, None

    @property
    def row_bins(self):
        """``[S * D, F_used]`` bins, rows over a 1-D mesh of ``D`` devices
        in contiguous ``S = ceil(N / D)``-row shards (rows past ``num_data``
        are padding), or None where the set holds one whole matrix.
        ``construct`` builds this, and no whole matrix, for a training set
        whose parameters name a row-sharded ``tree_learner``: each shard is
        quantized on the device that owns it."""
        self.construct()
        return self._row_bins

    def num_dense_columns(self) -> int:
        """Columns of the dense device bin matrix, whole or row-sharded."""
        self.construct()
        held = self._row_bins if self._row_bins is not None else self._bins
        return int(held.shape[1])

    def _row_mesh(self, config: Config):
        """The mesh a row-sharded learner will run this training set on,
        or None: one process, several devices, ``tree_learner`` data or
        voting. A validation set is scored whole and stays whole."""
        if (config.tree_learner not in ("data", "voting")
                or self.reference is not None
                or jax.process_count() > 1 or jax.device_count() < 2):
            return None
        from .parallel.data_parallel import make_mesh
        return make_mesh(axis="shard")

    def _place_row_shards(self, mesh, build) -> None:
        """``row_bins`` from ``build()`` under the span ``shard_place``,
        with the shards' row counts and the seconds in
        ``construct_stats``."""
        with profiling.span("shard_place") as sp:
            self._row_bins = jax.block_until_ready(build())
        d = mesh.devices.size
        s = self._row_bins.shape[0] // d
        self.construct_stats = {
            **(self.construct_stats or {}),
            "shard_rows_min": max(0, self.num_data - (d - 1) * s),
            "shard_rows_max": min(s, self.num_data),
            "shard_place_s": round(sp.seconds, 6)}

    # ------------------------------------------------------------ fields
    def set_label(self, label):
        self.label = label
        return self

    def set_weight(self, weight):
        self.weight = weight
        return self

    def set_group(self, group):
        self.group = group
        return self

    def set_init_score(self, init_score):
        self.init_score = init_score
        return self

    def set_field(self, name: str, data):
        if name == "label":
            self.label = data
        elif name == "weight":
            self.weight = data
        elif name == "group":
            self.group = data
        elif name == "init_score":
            self.init_score = data
        else:
            log.fatal(f"Unknown field: {name}")
        return self

    def get_field(self, name: str):
        return {"label": self.get_label(), "weight": self.get_weight(),
                "group": self.group, "init_score": self.init_score}[name]

    def get_label(self) -> Optional[np.ndarray]:
        return None if self.label is None else np.asarray(
            self.label.values if hasattr(self.label, "values") else self.label,
            dtype=np.float64).reshape(-1)

    def get_weight(self) -> Optional[np.ndarray]:
        return None if self.weight is None else np.asarray(
            self.weight, dtype=np.float64).reshape(-1)

    def get_group(self) -> Optional[np.ndarray]:
        if self.group is None:
            return None
        return np.asarray(self.group, dtype=np.int64).reshape(-1)

    def num_feature(self) -> int:
        self.construct()
        return self.num_total_features

    def get_feature_names(self) -> List[str]:
        self.construct()
        return self._feature_names

    # ------------------------------------------ reference API completeness
    def get_feature_name(self) -> List[str]:
        """reference: basic.py Dataset.get_feature_name."""
        return self.get_feature_names()

    def get_data(self):
        """Raw data if still held (reference: Dataset.get_data; raises the
        same way once free_raw_data has dropped it)."""
        if self._constructed and self.data is None:
            log.fatal("Cannot call get_data after freeing raw data, "
                      "set free_raw_data=False when constructing the Dataset")
        return self.data

    def get_init_score(self) -> Optional[np.ndarray]:
        return None if self.init_score is None else np.asarray(
            self.init_score, dtype=np.float64)

    def get_params(self) -> dict:
        """reference: Dataset.get_params (the dataset-relevant params)."""
        return dict(self.params)

    def get_ref_chain(self, ref_limit: int = 100):
        """The chain of reference datasets (reference: Dataset.get_ref_chain)."""
        chain, seen = [], set()
        cur = self
        while cur is not None and id(cur) not in seen \
                and len(chain) < ref_limit:
            chain.append(cur)
            seen.add(id(cur))
            cur = cur.reference
        return chain

    def set_feature_name(self, feature_name) -> "Dataset":
        """reference: Dataset.set_feature_name (pre-construct)."""
        if self._constructed:
            log.fatal("set_feature_name after construct is not supported")
        self.feature_name = list(feature_name)
        return self

    def set_categorical_feature(self, categorical_feature) -> "Dataset":
        """reference: Dataset.set_categorical_feature (pre-construct)."""
        if self._constructed:
            log.fatal("set_categorical_feature after construct is not "
                      "supported; pass it to the Dataset constructor")
        self.categorical_feature = categorical_feature
        return self

    def set_reference(self, reference: "Dataset") -> "Dataset":
        """reference: Dataset.set_reference (align to a train set's
        binning; pre-construct)."""
        if self._constructed:
            log.fatal("set_reference after construct is not supported")
        self.reference = reference
        return self

    def save_binary(self, filename: str) -> "Dataset":
        """Serialize to the .bin snapshot format the CLI's save_binary task
        writes (reference: Dataset.save_binary -> SaveBinaryFile; loadable
        with data=<file>.bin / lgb.Dataset(path))."""
        if self.data is None:
            log.fatal("save_binary needs the raw data (free_raw_data=False)")
        if _is_scipy_sparse(self.data):
            # the .bin format stores dense float arrays (cli._save_binary /
            # np.load with allow_pickle=False); a pickled sparse object
            # would save fine and then fail to load
            log.fatal("save_binary does not support scipy-sparse data")
        if self.label is None:
            log.fatal("save_binary needs a label")
        from .cli import _save_binary
        X = _to_2d_float(self._pandas_to_codes(self.data))
        _save_binary(filename, X, self.get_label(), self.get_weight(),
                     self.get_group(), self.get_init_score())
        return self

    def add_features_from(self, other: "Dataset") -> "Dataset":
        """Column-wise merge of another dataset's features (reference:
        Dataset.add_features_from). Both must still hold raw data; the
        merged dataset re-bins from scratch."""
        if self.data is None or other.data is None:
            log.fatal("add_features_from needs raw data on both datasets "
                      "(free_raw_data=False)")
        a = _to_2d_float(self._pandas_to_codes(self.data))
        b = _to_2d_float(other._pandas_to_codes(other.data))
        if a.shape[0] != b.shape[0]:
            log.fatal("add_features_from: row counts differ "
                      f"({a.shape[0]} vs {b.shape[0]})")
        self.data = np.column_stack([a, b])
        if self.feature_name not in ("auto", None) \
                and other.feature_name not in ("auto", None):
            self.feature_name = list(self.feature_name) + \
                list(other.feature_name)
        else:
            self.feature_name = "auto"
        # merge categorical designations (other's indices shift by our
        # original width); name-based entries carry over as-is
        def _cats(ds, offset):
            cf = ds.categorical_feature
            if cf in ("auto", None):
                return []
            return [c if isinstance(c, str) else int(c) + offset
                    for c in cf]
        merged = _cats(self, 0) + _cats(other, a.shape[1])
        if merged:
            self.categorical_feature = merged
        self._constructed = False
        return self

    # --------------------------------------------------------- construct
    def _resolve_categorical(self, num_features: int,
                             names: List[str]) -> List[int]:
        cf = self.categorical_feature
        if cf == "auto" or cf is None:
            # pandas categorical dtype capture (reference: basic.py:504-568)
            if hasattr(self.data, "dtypes"):
                return [i for i, dt in enumerate(self.data.dtypes)
                        if str(dt) in ("category",)]
            return []
        out = []
        for c in cf:
            if isinstance(c, str):
                if c in names:
                    out.append(names.index(c))
            else:
                out.append(int(c))
        return out

    def _pandas_to_codes(self, raw):
        """Convert pandas category-dtype columns to codes, capturing (train)
        or reusing (predict) the category lists so train and predict agree
        (reference: basic.py:504-568 _data_from_pandas pandas_categorical)."""
        if not hasattr(raw, "dtypes"):
            return raw
        import pandas as pd  # noqa: F401
        raw = raw.copy()
        for ci, col in enumerate(raw.columns):
            if str(raw[col].dtype) != "category":
                continue
            if ci in self.pandas_categorical:
                cats = self.pandas_categorical[ci]
                codes = pd.Categorical(raw[col], categories=cats).codes
            else:
                self.pandas_categorical[ci] = list(raw[col].cat.categories)
                codes = raw[col].cat.codes
            # unseen categories -> -1 -> NaN (routes to the other/NaN bin)
            raw[col] = np.where(np.asarray(codes) >= 0,
                                np.asarray(codes, dtype=np.float64), np.nan)
        return raw

    @classmethod
    def from_chunks(cls, chunks, label=None, reference: Optional["Dataset"]
                    = None, weight=None, group=None, init_score=None,
                    feature_name: Union[str, List[str]] = "auto",
                    categorical_feature: Union[str, List[int], List[str]]
                    = "auto",
                    params: Optional[Dict[str, Any]] = None,
                    free_raw_data: bool = True) -> "Dataset":
        """Dataset over a CHUNK STREAM instead of a monolithic matrix —
        the O(chunk)-host-memory construction front end (ISSUE 14). The
        raw feature matrix never materializes: construction runs two
        passes over the source (a streaming quantile/frequency sketch
        pass that fits the bin mappers, then a device bin pass writing
        each quantized chunk into its slot of the ``[N, F]`` bin matrix,
        H2D overlapped with host parsing).

        ``chunks`` is a callable returning a fresh iterator of chunks, a
        sequence of chunk arrays, or a 2-D array (sliced into
        ``construct_chunk_rows`` views). Each chunk is ``[rows, F]`` or
        an ``(X, y)`` pair — per-chunk labels concatenate into the
        dataset label (pass ``label=`` OR chunk labels, not both).
        Pre-partitioned multi-host loading wants
        ``distributed.load_partitioned_chunks`` instead (it merges the
        per-rank sketches over ``exchange_host``)."""
        ds = cls(None, label=label, reference=reference, weight=weight,
                 group=group, init_score=init_score,
                 feature_name=feature_name,
                 categorical_feature=categorical_feature, params=params,
                 free_raw_data=free_raw_data)
        ds._chunk_source = chunks
        return ds

    def construct(self, streaming: Optional[bool] = None) -> "Dataset":
        if self._constructed:
            return self
        # every path that builds runs under the span "construct" (and
        # the TIMETAG scope of the name); its stages are children
        with profiling.timer("construct"):
            return self._construct(streaming)

    def _construct(self, streaming: Optional[bool]) -> "Dataset":
        config = Config.from_params(self.params)
        stream = self._chunk_source is not None or (
            streaming if streaming is not None
            else config.construct_streaming)
        if stream:
            return self._construct_streaming(config)
        if _is_scipy_sparse(self.data) or (
                self.reference is not None
                and getattr(self.reference.construct(), "bundles", None)
                is not None):
            return self._construct_sparse(config)
        self.bundles = None
        if self.reference is not None:
            self.pandas_categorical = self.reference.construct().pandas_categorical
        with profiling.span("to_float"):
            # the host's float64 copy of the whole input
            raw = self._pandas_to_codes(self.data)
            X = _to_2d_float(raw)
        self.num_data, self.num_total_features = X.shape
        if self.feature_name == "auto" or self.feature_name is None:
            if hasattr(self.data, "columns"):
                self._feature_names = [str(c) for c in self.data.columns]
            else:
                self._feature_names = [f"Column_{i}" for i in range(self.num_total_features)]
        else:
            self._feature_names = list(self.feature_name)

        if self.reference is not None:
            ref = self.reference.construct()
            if self.num_total_features != ref.num_total_features:
                log.fatal("validation data has different number of features")
            self.mappers = ref.mappers
            self.used_features = ref.used_features
            self._feature_meta = ref._feature_meta
            self._missing_bin = ref._missing_bin
            self.max_num_bins = ref.max_num_bins
            self.has_categorical = ref.has_categorical
        else:
            cats = self._resolve_categorical(self.num_total_features, self._feature_names)
            forced = _load_forced_bins(config, self.num_total_features, cats)
            with profiling.span("find_bins"):
                self.mappers = binning.find_bin_mappers(X, config, cats,
                                                        forced_bounds=forced)
                self.used_features = np.array(
                    [j for j, m in enumerate(self.mappers)
                     if not m.is_trivial], dtype=np.int32)
                if len(self.used_features) == 0:
                    log.warning("There are no meaningful features, as all "
                                "feature values are constant.")
                self._build_feature_meta(config)

        used = [self.mappers[j] for j in self.used_features]
        dtype = np.uint8 if self.max_num_bins <= 256 else np.int32
        raw_np = raw.values if hasattr(raw, "values") else raw
        # float32 input on a TPU backend quantizes ON DEVICE (bit-exact vs
        # the host path, see binning.device_bin_tables): the host
        # searchsorted loop is the construct bottleneck on small hosts
        # (reference bins at memory speed with OpenMP, dense_bin.hpp)
        use_device = (jax.default_backend() == "tpu"
                      and len(self.used_features)
                      and isinstance(raw_np, np.ndarray) and raw_np.ndim == 2
                      and raw_np.dtype == np.float32
                      and all(m.bin_type == binning.BIN_TYPE_NUMERICAL
                              for m in used))
        # which quantiser ran: no CPU test reaches the device one, so
        # chip_smoke.py reads this and checks a slice against the host's
        self.binned_on_device = bool(use_device)
        mesh = self._row_mesh(config) if len(self.used_features) else None
        # quantise and place; a row-sharded set does both inside its
        # "shard_place". Neither waits for the device: what the upload and
        # the device quantiser leave running shows where bins is first read
        with profiling.span("bin_rows"):
            if use_device:
                Xu32 = raw_np if len(used) == raw_np.shape[1] \
                    else np.ascontiguousarray(raw_np[:, self.used_features])
                if mesh is not None:
                    self._place_row_shards(
                        mesh, lambda: binning.bin_data_device(
                            Xu32, used, mesh=mesh))
                else:
                    self.bins = binning.bin_data_device(Xu32, used)
            else:
                Xu = X[:, self.used_features] if len(self.used_features) \
                    else np.zeros((self.num_data, 0))
                bins_np = binning.bin_data(Xu, used).astype(dtype)
                bins_np = self._maybe_extract_sparse(bins_np, config)
                if mesh is not None:
                    self._place_row_shards(
                        mesh,
                        lambda: binning.place_row_shards(bins_np, mesh))
                else:
                    self.bins = jnp.asarray(bins_np)
        # raw feature retention for linear trees (reference: dataset.h:720
        # raw_data_, kept when linear_tree so leaves can fit linear models)
        keep_raw = config.linear_tree or (
            self.reference is not None
            and getattr(self.reference, "raw_data_np", None) is not None)
        self.raw_data_np = X.astype(np.float32) if keep_raw else None
        self._constructed = True
        if self.free_raw_data:
            self.data = None
        total_bins = int(sum(m.num_bin for m in used))
        log.info(f"Total Bins {total_bins}")
        log.info(f"Number of data points in the train set: {self.num_data}, "
                 f"number of used features: {len(self.used_features)}")
        return self

    # ------------------------------------------------ streaming construct
    def _construct_streaming(self, config: Config) -> "Dataset":
        """Two-pass chunked construction: host memory is O(chunk), never
        O(N*F) raw (the 10.5M-row monolithic construct held a 1.2 GB f32
        matrix before binning; at 100M rows that ceiling is fatal —
        ROADMAP item 2).

        Pass 1 (``sketch_pass``): fold each chunk into per-feature
        mergeable :class:`binning.FeatureSketch` es and fit BinMappers
        from the merged summaries — bit-identical to the sampled
        ``find_bin_mappers`` whenever one chunk covers the sample (the
        sketches stay exact and the sample is all rows). Pass 2
        (``bin_pass``): quantize each chunk on device and write it into
        its row slot of the preallocated bin matrix
        (:class:`binning.StreamingBinWriter`), the async dispatch queue
        double-buffering chunk k's H2D against chunk k+1's host parse;
        the blocking drain at the end is the ``h2d_overlap`` sub-scope.
        Non-float32 or categorical-bearing streams take a host per-chunk
        ``bin_data`` fallback (same O(chunk) raw residency).

        Always-on gauges: ``construct_sketch_s`` / ``construct_bin_s`` /
        ``construct_h2d_overlap_s`` / ``construct_peak_bytes`` (max raw
        chunk bytes resident, <= 2 chunks) / ``construct_rows`` — the
        flight-recorder header and bench.py's construct fields read them
        (telemetry.construct_snapshot). EFB bundling and sparse-column
        extraction do not apply (dense chunk input, like the dense
        monolithic path); ``linear_tree`` needs the raw matrix resident
        and is rejected."""
        import time as _time

        if config.linear_tree:
            log.fatal("linear_tree keeps the raw matrix resident and is "
                      "not supported with streaming construction")
        source = self._chunk_source if self._chunk_source is not None \
            else self.data
        if _is_scipy_sparse(source) or hasattr(source, "dtypes"):
            log.fatal("streaming construction supports dense arrays or "
                      "chunk sources only (scipy-sparse and pandas input "
                      "take the monolithic paths)")
        # the process-level construct_* gauges describe the LAST streaming
        # construction (bench/smoke read them right after constructing);
        # per-dataset attribution rides self.construct_stats instead
        profiling.drop_gauges("construct_")
        factory = binning.chunk_factory(source, config.construct_chunk_rows)
        peak = [0]

        def track(nbytes, mult=1):
            peak[0] = max(peak[0], mult * int(nbytes))

        t0 = _time.time()
        # aligned valid sets take the LIGHT pass (fold=False): their
        # mappers come from the reference, so only row/size/label
        # accounting (and the mid-stream width check) is needed — the
        # per-column fold is the dominant sketch wall
        with profiling.timer("sketch_pass"):
            sketches, num_data, sizes, chunk_labels = binning.sketch_chunks(
                factory, max_size=config.sketch_max_size, track_bytes=track,
                fold=self.reference is None)
        num_features = len(sketches)
        if self.reference is not None:
            sketches = None
        sketch_s = _time.time() - t0
        self.num_data, self.num_total_features = num_data, num_features
        if chunk_labels is not None:
            if self.label is not None:
                log.fatal("labels were passed both to the Dataset and in "
                          "the chunk stream; pass one or the other")
            self.label = chunk_labels
        if self.feature_name == "auto" or self.feature_name is None:
            self._feature_names = [f"Column_{i}"
                                   for i in range(self.num_total_features)]
        else:
            self._feature_names = list(self.feature_name)
        self.bundles = None

        if self.reference is not None:
            ref = self.reference.construct()
            if getattr(ref, "bundles", None) is not None:
                log.fatal("streaming construction cannot align to an "
                          "EFB-bundled reference dataset")
            if self.num_total_features != ref.num_total_features:
                log.fatal("validation data has different number of features")
            self.mappers = ref.mappers
            self.used_features = ref.used_features
            self._feature_meta = ref._feature_meta
            self._missing_bin = ref._missing_bin
            self.max_num_bins = ref.max_num_bins
            self.has_categorical = ref.has_categorical
            self.pandas_categorical = ref.pandas_categorical
        else:
            cats = self._resolve_categorical(self.num_total_features,
                                             self._feature_names)
            forced = _load_forced_bins(config, self.num_total_features, cats)
            self.mappers = binning.fit_mappers_from_sketches(
                sketches, num_data, config, cats, forced_bounds=forced)
            self.used_features = np.array(
                [j for j, m in enumerate(self.mappers) if not m.is_trivial],
                dtype=np.int32)
            if len(self.used_features) == 0:
                log.warning("There are no meaningful features, as all "
                            "feature values are constant.")
            self._build_feature_meta(config)
        del sketches

        used = [self.mappers[j] for j in self.used_features]
        uf = self.used_features
        all_numeric = all(m.bin_type == binning.BIN_TYPE_NUMERICAL
                          for m in used)
        max_chunk = max(sizes) if sizes else 1
        t0 = _time.time()
        overlap_s = 0.0
        # device writer only for float32 streams: it is bit-exact vs the
        # host path for f32 input (device_bin_tables), while a silent
        # f64 -> f32 cast could move values across bin bounds
        it = iter(factory())
        first_chunk = next(it, None)
        if first_chunk is None:
            log.fatal("chunk source yielded no chunks on the bin pass "
                      "(but did on the sketch pass): the source must be "
                      "re-iterable — a callable must return a FRESH "
                      "iterator per call, not a shared one-shot "
                      "generator")
        first = binning.split_chunk(first_chunk)[0]
        first_chunk = None
        use_device = (all_numeric and len(used)
                      and isinstance(first, np.ndarray)
                      and first.dtype == np.float32)
        if use_device:
            writer = binning.StreamingBinWriter(used, num_data, max_chunk)
            staged_bytes = writer.chunk_pad * writer.f * 4

            def _write(X):
                if X.dtype != np.float32:
                    # the f32 device-path decision was made on the FIRST
                    # chunk; a later wider-dtype chunk silently cast to
                    # f32 could land values in the wrong bin
                    log.fatal(
                        f"chunk dtype changed mid-stream ({X.dtype} after "
                        f"float32): streaming construction requires a "
                        f"uniform chunk dtype — make every chunk float32, "
                        f"or every chunk float64 for the exact host path")
                if len(uf) == X.shape[1]:
                    # resident: the source chunk + the in-flight staged copy
                    track(X.nbytes + staged_bytes)
                    writer.write(X)
                else:
                    Xu = np.ascontiguousarray(X[:, uf])
                    # resident: chunk + column-subset copy + staged copy
                    track(X.nbytes + Xu.nbytes + staged_bytes)
                    writer.write(Xu)

            with profiling.timer("bin_pass"):
                _write(first)
                first = None
                while True:                    # ref-dropping next() loop
                    chunk = next(it, None)
                    if chunk is None:
                        break
                    X = binning.split_chunk(chunk)[0]
                    chunk = None
                    _write(X)
                    X = None
                t1 = _time.time()
                with profiling.timer("h2d_overlap"):
                    self.bins = writer.finalize()
                overlap_s = _time.time() - t1
        else:
            dtype = np.uint8 if self.max_num_bins <= 256 else np.int32
            bins_np = np.zeros((num_data, max(len(uf), 1)), dtype)
            first = it = None              # host helper re-iterates itself
            with profiling.timer("bin_pass"):
                binning.bin_chunks_host(factory, used, uf, bins_np, track)
                t1 = _time.time()
                with profiling.timer("h2d_overlap"):
                    self.bins = jnp.asarray(bins_np)
                    jax.block_until_ready(self.bins)
                overlap_s = _time.time() - t1
        bin_s = _time.time() - t0

        profiling.set_gauge("construct_sketch_s", sketch_s)
        profiling.set_gauge("construct_bin_s", bin_s)
        profiling.set_gauge("construct_h2d_overlap_s", overlap_s)
        profiling.set_gauge("construct_peak_bytes", float(peak[0]))
        profiling.set_gauge("construct_rows", float(num_data))
        # per-dataset attribution (the flight-recorder header reads THIS,
        # not the process gauges, so a later construct cannot steal or
        # wipe the training set's stats)
        self.construct_stats = {
            "sketch_pass": round(sketch_s, 6),
            "bin_pass": round(bin_s, 6),
            "h2d_overlap": round(overlap_s, 6),
            "peak_host_bytes": int(peak[0]),
            "rows": int(num_data),
        }
        # no monolithic raw reference may survive a streaming construct
        # (the whole point is that it never existed)
        self.drop_streams()
        self.raw_data_np = None
        self._constructed = True
        if self.free_raw_data:
            self.data = None
            self._chunk_source = None
        total_bins = int(sum(m.num_bin for m in used))
        log.info(f"Total Bins {total_bins}")
        log.info(f"Number of data points in the train set: {self.num_data},"
                 f" number of used features: {len(self.used_features)} "
                 f"(streaming construct: {len(sizes)} chunks, peak raw "
                 f"{peak[0]} bytes, sketch {sketch_s:.2f}s + bin "
                 f"{bin_s:.2f}s, drain {overlap_s:.2f}s)")
        return self

    @property
    def has_sparse_cols(self) -> bool:
        return self.sp_cols is not None and len(self.sp_cols) > 0

    def drop_streams(self) -> None:
        """Forget the stream storage; all five fields go together, so that
        ``has_sparse_cols`` never speaks for streams that are gone."""
        self.sp_cols = self.sp_rows = self.sp_cell = None
        self.sp_default = self.sp_offsets = None

    def stream_column(self, i: int) -> Tuple[np.ndarray, np.ndarray]:
        """``(rows, bins)`` of stream ``i`` (device column ``sp_cols[i]``)
        on the host: its non-default entries, rows ascending. What every
        host reader of the streams goes through."""
        a, b = int(self.sp_offsets[i]), int(self.sp_offsets[i + 1])
        return (np.asarray(self.sp_rows)[a:b],
                np.asarray(self.sp_cell)[a:b] - i * self.max_num_bins)

    def _maybe_extract_sparse(self, bins_np: np.ndarray,
                              config: Config) -> np.ndarray:
        """Sparse device storage for heavily-concentrated columns — the TPU
        re-design of the reference's SparseBin (reference: sparse_bin.hpp
        delta/val streams chosen when sparse_rate > kSparseThreshold=0.7,
        bin.h:39, with the elided most-frequent bin reconstructed by
        FixHistogram, dataset.cpp FixHistogram decl dataset.h:506).

        A device column whose most-frequent bin covers >= 90% of rows is
        dropped from the dense [N, F] matrix and stored as a (row, bin)
        stream of its NON-default entries only; histogram planes for these
        columns scatter-add O(nnz) entries per pass and the default-bin
        cell is reconstructed from the per-leaf totals (exactly the
        reference's most_freq elision + FixHistogram).
        The threshold is 0.9 (not the reference's 0.7): a stream entry
        costs 8 bytes (int32 row + int32 cell) against 1 byte/row dense, so
        the memory break-even sits near 88% concentration, and TPU
        scatter-adds are slow enough that the pass-cost win also needs the
        nnz fraction small. Applies to the primary training dataset on the
        serial learner only: aligned validation sets stay dense (their
        bins are traversed per tree), and the distributed learners shard
        dense columns.

        Layout: ONE concatenation of the streams, no padding. Stream ``i``
        is device column ``sp_cols[i]``, its default bin ``sp_default[i]``
        and its entries ``[sp_offsets[i], sp_offsets[i + 1])`` of
        ``sp_rows`` (int32 row ids) and ``sp_cell`` (int32
        ``i * max_num_bins + bin``: the entry's cell in one leaf's
        ``[F_sp, B]`` block of the planes, so that a histogram pass adds
        only the leaf slot's base); ``sp_offsets`` is a host array of
        F_sp + 1 and ``sp_offsets[-1]`` the entry count E. The streams are
        ordered by ASCENDING length (ties by column), the widest last, so
        ``sp_cols`` is not ascending.

        What the grower counts on (``grower._apply_split``): inside a
        stream the rows ASCEND strictly (they come from ``np.nonzero``),
        and a slice of the widest stream's length M taken at ANY stream's
        start stays inside the arrays (the widest is last, so
        ``sp_offsets[i] + M <= E``). A split on stream ``i`` takes that
        slice, turns the positions at or past the stream's own length into
        row ``n`` (out of range: dropped) and rebuilds the column by one
        N-row scatter that does not sort its indices first (4.9 ms at 11M
        rows and 0.8M entries, 5.7 with the sort); a split on a dense
        column pays nothing for the streams. No reader sees a padded slot:
        there is none.
        """
        threshold, min_rows = 0.90, 512
        if (not config.is_enable_sparse or self.reference is not None
                or config.linear_tree
                or getattr(self, "is_pre_partitioned", False)
                or str(config.tree_learner or "serial") != "serial"
                # dart (drop-score re-traversal) and rf (mean rollback)
                # re-traverse the TRAIN bins with logical feature ids,
                # which sparse storage no longer materializes full-width
                or str(config.boosting or "gbdt") in ("dart", "rf",
                                                      "random_forest")):
            return bins_np
        n, fc = bins_np.shape
        if n < min_rows or fc == 0:
            return bins_np
        found = []                        # (entries, column, default bin)
        for c in range(fc):
            cnt = np.bincount(bins_np[:, c].astype(np.int64))
            mode = int(np.argmax(cnt))
            if cnt[mode] >= threshold * n:
                found.append((n - int(cnt[mode]), c, mode))
        if not found:
            return bins_np
        found.sort()                      # ascending length, widest last
        offsets = np.concatenate(
            [[0], np.cumsum([k for k, _, _ in found])]).astype(np.int64)
        rows = np.empty((offsets[-1],), dtype=np.int32)
        cell = np.empty((offsets[-1],), dtype=np.int32)
        for i, (_, c, mode) in enumerate(found):
            nz = np.nonzero(bins_np[:, c] != mode)[0]     # ascending
            a, b = offsets[i], offsets[i + 1]
            rows[a:b] = nz
            cell[a:b] = bins_np[nz, c].astype(np.int32) \
                + i * self.max_num_bins
        sp = [c for _, c, _ in found]
        self.sp_cols = np.asarray(sp, dtype=np.int32)
        self.sp_offsets = offsets
        self.sp_rows = jnp.asarray(rows)
        self.sp_cell = jnp.asarray(cell)
        self.sp_default = jnp.asarray(
            np.asarray([mode for _, _, mode in found], np.int32))
        dense_cols = np.asarray([c for c in range(fc) if c not in set(sp)],
                                dtype=np.int32)
        log.info(f"sparse storage: {len(sp)} of {fc} device columns "
                 f"({offsets[-1]} non-default entries, the widest "
                 f"{found[-1][0]}; >= {threshold:.0%} concentrated)")
        return np.ascontiguousarray(bins_np[:, dense_cols])

    # ------------------------------------------------- sparse + EFB path
    def _construct_sparse(self, config: Config) -> "Dataset":
        """Construct from scipy sparse input (and/or with EFB bundling)
        without ever densifying the raw matrix (reference: sparse_bin.hpp
        storage + dataset.cpp:239 FastFeatureBundling; here sparse features
        bundle into shared dense device columns, which is the TPU-correct
        storage: a dense [N, G] bin matrix with G ~ bundles, not features)."""
        if config.linear_tree:
            log.fatal("linear_tree is not supported with sparse input")
        sparse = _is_scipy_sparse(self.data)
        if sparse:
            X = self.data.tocsc()
        else:
            with profiling.span("to_float"):
                X = _to_2d_float(self._pandas_to_codes(self.data))
        self.num_data, self.num_total_features = X.shape
        seconds: Dict[str, float] = {}
        if self.feature_name == "auto" or self.feature_name is None:
            self._feature_names = [f"Column_{i}"
                                   for i in range(self.num_total_features)]
        else:
            self._feature_names = list(self.feature_name)

        if self.reference is not None:
            ref = self.reference.construct()
            if self.num_total_features != ref.num_total_features:
                log.fatal("validation data has different number of features")
            for attr in ("mappers", "used_features", "_feature_meta",
                         "_missing_bin", "max_num_bins", "has_categorical",
                         "bundles", "_bundle_meta", "_owner_orig",
                         "_thr_fwd", "_thr_rev", "pandas_categorical"):
                setattr(self, attr, getattr(ref, attr, None))
        else:
            cats = self._resolve_categorical(self.num_total_features,
                                             self._feature_names)
            sample = binning.sample_indices(
                self.num_data, config.bin_construct_sample_cnt,
                config.data_random_seed)
            if sparse:
                Xs = self.data.tocsr()[sample].tocsc()
            else:
                Xs = X[sample]
            forced = _load_forced_bins(config, self.num_total_features, cats)
            with _stage(seconds, "efb_fit_mappers"):
                self.mappers = self._fit_mappers_from_sample(
                    Xs, len(sample), config, cats, forced)
            self.used_features = np.array(
                [j for j, m in enumerate(self.mappers) if not m.is_trivial],
                dtype=np.int32)
            if len(self.used_features) == 0:
                log.warning("There are no meaningful features, as all feature"
                            " values are constant.")
            with _stage(seconds, "efb_find_bundles"):
                self._run_bundling(Xs, len(sample), config)
                self._build_feature_meta_bundled(config)

        dtype = np.uint8 if self.max_num_bins <= 256 else np.int32
        with _stage(seconds, "efb_place"):
            if self.bundles is None:
                # reference was constructed dense (no EFB bundles): bin
                # through the per-feature mappers column-wise so this
                # sparse valid set aligns with the reference's [N, F_used]
                bins_np = self._bin_columns_unbundled(X)
            else:
                bins_np = self._bin_columns(X)
            bins_np = bins_np.astype(dtype)
        with _stage(seconds, "sparse_extract"):
            bins_np = self._maybe_extract_sparse(bins_np, config)
        self.bins = jnp.asarray(bins_np)
        if self.reference is None:
            # a training set's own; a set aligned to it reports nothing
            multi = [b for b in self.bundles if len(b.members) > 1]
            has = self.has_sparse_cols
            self.construct_stats = {
                "efb_used_features": len(self.used_features),
                "efb_columns": len(self.bundles),
                "efb_bundle_bins": int(sum(b.num_bin for b in multi)),
                "efb_conflict_rows": self._efb_conflict_rows,
                "sparse_stream_columns": len(self.sp_cols) if has else 0,
                # entries that exist, and slots the device arrays hold for
                # them: the layout keeps no padding and no tail
                "sparse_stream_entries": (int(self.sp_offsets[-1])
                                          if has else 0),
                "sparse_stream_slots": (int(self.sp_rows.shape[0])
                                        if has else 0),
                **{k: round(v, 6) for k, v in seconds.items()}}
        self.raw_data_np = None
        self._constructed = True
        if self.free_raw_data:
            self.data = None
        g = len(self.bundles) if self.bundles else 0
        nb_total = sum(b.num_bin for b in (self.bundles or []))
        log.info(f"Total Bins {nb_total}")
        log.info(f"Number of data points in the train set: {self.num_data}, "
                 f"number of used features: {len(self.used_features)}"
                 + (f" (bundled into {g} columns)"
                    if g and g != len(self.used_features) else ""))
        return self

    def _fit_mappers_from_sample(self, Xs, total, config, cats,
                                 forced_bounds=None):
        """Per-feature BinMapper from a row sample; for CSC input only the
        nonzeros are touched (zeros implied by the count, the reference's
        sparse sampling protocol, dataset_loader.cpp:953+)."""
        sparse = _is_scipy_sparse(Xs)
        filter_cnt = binning.filter_cnt_for_sample(config, total,
                                                   self.num_data)
        cat_set = set(int(c) for c in cats)
        mappers = []
        for j in range(self.num_total_features):
            if sparse:
                vals = np.asarray(
                    Xs.data[Xs.indptr[j]:Xs.indptr[j + 1]], dtype=np.float64)
            else:
                col = np.asarray(Xs[:, j], dtype=np.float64)
                vals = col[col != 0.0]
            mappers.append(binning.fit_mapper_for_column(
                j, vals, total, config, cat_set, filter_cnt, forced_bounds))
        return mappers

    def _run_bundling(self, Xs, total, config) -> None:
        """Greedy EFB over the bundle-eligible used features
        (reference: dataset.cpp:239 FastFeatureBundling)."""
        from .bundling import Bundle, fast_feature_bundling
        used = self.used_features
        mc = list(config.monotone_constraints or [])
        fc = list(config.feature_contri or [])
        sparse = _is_scipy_sparse(Xs)
        num_bins = []
        nonzero_rows = []
        bundle_ok = np.zeros(len(used), dtype=bool)
        for i, j in enumerate(used):
            m = self.mappers[j]
            num_bins.append(m.num_bin)
            ok = (config.enable_bundle
                  and m.bin_type == binning.BIN_TYPE_NUMERICAL
                  and m.missing_type != binning.MISSING_NAN
                  and m.most_freq_bin == m.default_bin
                  and not (j < len(mc) and int(mc[j]) != 0)
                  and not (j < len(fc) and float(fc[j]) != 1.0))
            if not ok:
                nonzero_rows.append(None)
                continue
            if sparse:
                rows = Xs.indices[Xs.indptr[j]:Xs.indptr[j + 1]]
                vals = np.asarray(Xs.data[Xs.indptr[j]:Xs.indptr[j + 1]],
                                  dtype=np.float64)
            else:
                col = np.asarray(Xs[:, j], dtype=np.float64)
                rows = np.nonzero(col != 0.0)[0]
                vals = col[rows]
            b = m.values_to_bins(vals)
            nonzero_rows.append(np.asarray(rows)[b != m.most_freq_bin])
            bundle_ok[i] = True
        self.bundles = fast_feature_bundling(nonzero_rows, num_bins,
                                             bundle_ok, total)

    def _build_feature_meta_bundled(self, config: Config) -> None:
        """Per-COLUMN metadata for bundled datasets: each device column is a
        bundle (or a single feature); bundle columns get segment arrays for
        the EFB-aware split search (ops/split.py BundleMeta)."""
        from .ops.split import BundleMeta
        used = self.used_features
        bundles = self.bundles
        g = max(len(bundles), 1)
        nb = np.full(g, 2, np.int32)
        missing = np.zeros(g, np.int32)
        default_bin = np.zeros(g, np.int32)
        is_cat = np.zeros(g, bool)
        monotone = np.zeros(g, np.int8)
        penalty = np.ones(g, np.float32)
        missing_bin = np.full(g, -1, np.int32)
        mc = list(config.monotone_constraints or [])
        fc = list(config.feature_contri or [])
        for gi, bd in enumerate(bundles):
            if len(bd.members) == 1:
                j = int(used[bd.members[0]])
                m = self.mappers[j]
                nb[gi] = m.num_bin
                missing[gi] = m.missing_type
                default_bin[gi] = m.default_bin
                is_cat[gi] = m.bin_type == binning.BIN_TYPE_CATEGORICAL
                if j < len(mc):
                    monotone[gi] = np.int8(mc[j])
                if j < len(fc):
                    penalty[gi] = np.float32(fc[j])
                mode_a = (m.num_bin > 2
                          and m.missing_type != binning.MISSING_NONE)
                if mode_a and m.missing_type == binning.MISSING_NAN:
                    missing_bin[gi] = m.num_bin - 1
                elif mode_a and m.missing_type == binning.MISSING_ZERO:
                    missing_bin[gi] = m.default_bin
            else:
                nb[gi] = bd.num_bin
        self.max_num_bins = int(nb.max()) if len(bundles) else 2
        b = self.max_num_bins
        seg_lo = np.zeros((g, b), np.int32)
        seg_hi = np.zeros((g, b), np.int32)
        is_bundle = np.zeros(g, bool)
        fwd_ok = np.zeros((g, b), bool)
        rev_ok = np.zeros((g, b), bool)
        owner_orig = np.zeros((g, b), np.int32)
        thr_fwd = np.tile(np.arange(b, dtype=np.int32), (g, 1))
        thr_rev = np.tile(np.arange(b, dtype=np.int32), (g, 1))
        # tie-break preference tables (higher wins among equal-gain
        # candidates), ordered by the candidate's ORIGINAL feature index
        # first so within-bundle and cross-column ties resolve exactly as
        # the unbundled scan's feature-major order would (ops/split.py
        # BundleMeta docstring; without these a within-bundle tie goes to
        # the highest-offset member — the opposite of the unbundled run)
        u = int(self.num_total_features)
        pref_fwd = np.zeros((g, b), np.int32)
        pref_rev = np.zeros((g, b), np.int32)

        def _owner_base(j):
            return (u - 1 - j) * 4 * b

        for gi, bd in enumerate(bundles):
            if len(bd.members) == 1:
                j = int(used[bd.members[0]])
                seg_hi[gi, :] = nb[gi] - 1
                owner_orig[gi, :] = j
                # plain column: the standard rev-first / high-threshold /
                # fwd low-threshold order, keyed by the original feature
                t = np.arange(b, dtype=np.int32)
                pref_rev[gi, :] = _owner_base(j) + 2 * b + t
                pref_fwd[gi, :] = _owner_base(j) + (b - 1) - t
                continue
            is_bundle[gi] = True
            # per-bin candidate masks reproducing each member's UNBUNDLED
            # scan exactly: the member's most-frequent mass (reconstructed
            # from leaf totals) sits at its ordinal position z, so forward
            # candidates are thresholds below z (mass right) and reverse
            # candidates thresholds at/above z (mass left); the leading
            # phantom bin hosts the z-only-left candidate when z == 0
            for mi, off in zip(bd.members, bd.offsets):
                j = int(used[mi])
                m = self.mappers[j]
                nbm = m.num_bin
                z = m.most_freq_bin
                span = nbm                      # phantom + (nbm - 1) data
                seg_lo[gi, off:off + span] = off
                seg_hi[gi, off:off + span] = off + span - 1
                owner_orig[gi, off:off + span] = j
                r = np.arange(nbm - 1)          # data-bin ranks
                dslice = slice(off + 1, off + span)
                mode_zero = (m.missing_type == binning.MISSING_ZERO
                             and nbm > 2)
                if mode_zero:
                    # zero-as-missing member: both directions, default-bin
                    # threshold skipped (SKIP_DEFAULT_BIN semantics)
                    t_orig = r + (r >= z)
                    ok = t_orig <= nbm - 2
                    fwd_ok[gi, dslice] = ok
                    rev_ok[gi, dslice] = ok
                    thr_fwd[gi, dslice] = t_orig
                    thr_rev[gi, dslice] = t_orig
                    # unbundled mode-A scan order: rev first (high
                    # threshold wins), fwd on strictly-greater only
                    pref_rev[gi, dslice] = _owner_base(j) + 2 * b + t_orig
                    pref_fwd[gi, dslice] = _owner_base(j) + (b - 1) - t_orig
                else:
                    fwd_ok[gi, dslice] = r < z
                    rev_ok[gi, dslice] = (r >= z - 1) & (r <= nbm - 3)
                    thr_fwd[gi, dslice] = r
                    thr_rev[gi, dslice] = r + 1
                    # the member's UNBUNDLED scan is a single REVERSE pass
                    # (missing_type none): every candidate — including the
                    # ones the bundle must evaluate as forward-direction —
                    # competes with the rev preference of its original
                    # threshold, so ties resolve to the highest threshold
                    # like the plain column's scan
                    pref_fwd[gi, dslice] = _owner_base(j) + 2 * b + r
                    pref_rev[gi, dslice] = _owner_base(j) + 2 * b + (r + 1)
                    if z == 0:                  # phantom: left = z mass only
                        rev_ok[gi, off] = True
                        thr_rev[gi, off] = 0
                        pref_rev[gi, off] = _owner_base(j) + 2 * b
        self._bundle_meta = BundleMeta(seg_lo=jnp.asarray(seg_lo),
                                       seg_hi=jnp.asarray(seg_hi),
                                       is_bundle=jnp.asarray(is_bundle),
                                       fwd_ok=jnp.asarray(fwd_ok),
                                       rev_ok=jnp.asarray(rev_ok),
                                       pref_fwd=jnp.asarray(pref_fwd),
                                       pref_rev=jnp.asarray(pref_rev))
        self._owner_orig = owner_orig
        self._thr_fwd = thr_fwd
        self._thr_rev = thr_rev
        self.has_categorical = bool(is_cat.any())
        self._feature_meta = FeatureMeta(
            num_bins=jnp.asarray(nb),
            missing_type=jnp.asarray(missing),
            default_bin=jnp.asarray(default_bin),
            is_categorical=jnp.asarray(is_cat),
            monotone=jnp.asarray(monotone),
            penalty=jnp.asarray(penalty),
        )
        self._missing_bin = jnp.asarray(missing_bin)

    def _bin_columns(self, X) -> np.ndarray:
        """Raw matrix -> bundled bin matrix [N, G] (the analog of
        FeatureGroup::PushData placement, feature_group.h). Leaves in
        ``_efb_conflict_rows`` the rows in which two members of one bundle
        are both off their most-frequent bin, where the later member's bin
        overwrites the earlier one's (EFB's stated approximation, bounded
        on the SAMPLE by the conflict budget)."""
        sparse = _is_scipy_sparse(X)
        if sparse:
            X = X.tocsc()
            n = X.shape[0]
        else:
            X = _to_2d_float(X)
            n = X.shape[0]
        used = self.used_features
        g = len(self.bundles) if self.bundles else 0
        out = np.zeros((n, max(g, 1)), dtype=np.int32)
        conflicts = 0
        for gi, bd in enumerate(self.bundles or []):
            taken = []          # rows a later member found already placed
            for mi, off in zip(bd.members, bd.offsets):
                j = int(used[mi])
                m = self.mappers[j]
                if sparse:
                    rows = X.indices[X.indptr[j]:X.indptr[j + 1]]
                    vals = np.asarray(X.data[X.indptr[j]:X.indptr[j + 1]],
                                      dtype=np.float64)
                else:
                    col = np.asarray(X[:, j], dtype=np.float64)
                    rows = np.nonzero((col != 0.0) | np.isnan(col))[0]
                    vals = col[rows]
                if len(bd.members) == 1:
                    out[:, gi] = m.default_bin
                    if len(rows):
                        out[rows, gi] = m.values_to_bins(vals)
                else:
                    bvals = m.values_to_bins(vals)
                    sel = bvals != m.most_freq_bin
                    bb = bvals[sel]
                    bb = bb - (bb > m.most_freq_bin)
                    at = np.asarray(rows)[sel]
                    taken.append(at[out[at, gi] != 0])
                    # +1: data bins follow the member's phantom candidate bin
                    out[at, gi] = off + 1 + bb
            if taken:
                conflicts += len(np.unique(np.concatenate(taken)))
        self._efb_conflict_rows = conflicts
        return out

    def _bin_columns_unbundled(self, X) -> np.ndarray:
        """Raw matrix -> UNBUNDLED bin matrix [N, F_used] through the
        per-feature mappers, column-wise without densifying sparse input
        (the valid-against-dense-reference path: the reference has no EFB
        bundles, so device column i is used feature i directly)."""
        assert _is_scipy_sparse(X), "dense input takes the dense bin path"
        X = X.tocsc()
        n = X.shape[0]
        f = max(len(self.used_features), 1)
        out = np.zeros((n, f), dtype=np.int32)
        for i, j in enumerate(self.used_features):
            j = int(j)
            m = self.mappers[j]
            rows = X.indices[X.indptr[j]:X.indptr[j + 1]]
            vals = np.asarray(X.data[X.indptr[j]:X.indptr[j + 1]],
                              dtype=np.float64)
            # implicit zeros take the bin of value 0 (bin.h GetDefaultBin)
            out[:, i] = m.default_bin
            if len(rows):
                out[rows, i] = m.values_to_bins(vals)
        return out

    def unbundled_bins(self, start: int, stop: int) -> np.ndarray:
        """Rows ``[start, stop)`` of the device storage decoded back to one
        bin a USED feature, int32 ``[stop - start, F_used]``: dense columns
        and (row, bin) streams alike, a bundle's bin mapped to its owning
        member's own bin and to every other member's most-frequent bin.
        Equal to what the host quantiser gives unbundled
        (``_bin_columns_unbundled``) but in a row where two members of one
        bundle collide (``efb_conflict_rows``): what a check holds the
        device storage to."""
        self.construct()
        k = stop - start
        g = self.num_used_features()
        cols = np.asarray(self.bins[start:stop]).astype(np.int32)
        if self.has_sparse_cols:
            dense, cols = cols, np.empty((k, g), np.int32)
            sp = np.asarray(self.sp_cols)
            cols[:, np.setdiff1d(np.arange(g), sp)] = dense
            cols[:, sp] = np.asarray(self.sp_default)[None, :]
            for i, c in enumerate(sp):
                rows, vals = self.stream_column(i)
                a, b = np.searchsorted(rows, [start, stop])
                cols[rows[a:b] - start, c] = vals[a:b]
        if self.bundles is None:
            return cols
        out = np.empty((k, len(self.used_features)), np.int32)
        for gi, bd in enumerate(self.bundles):
            if len(bd.members) == 1:
                out[:, bd.members[0]] = cols[:, gi]
                continue
            for mi, off in zip(bd.members, bd.offsets):
                m = self.mappers[int(self.used_features[mi])]
                r = cols[:, gi] - (off + 1)      # rank among the data bins
                own = (r >= 0) & (r < m.num_bin - 1)
                out[:, mi] = np.where(own, r + (r >= m.most_freq_bin),
                                      m.most_freq_bin)
        return out

    @property
    def bundle_meta(self):
        self.construct()
        return getattr(self, "_bundle_meta", None) \
            if self.bundles is not None else None

    def _build_feature_meta(self, config: Config):
        used = [self.mappers[j] for j in self.used_features]
        nb = np.array([m.num_bin for m in used], dtype=np.int32)
        self.max_num_bins = int(nb.max()) if len(nb) else 2
        missing = np.array([m.missing_type for m in used], dtype=np.int32)
        default_bin = np.array([m.default_bin for m in used], dtype=np.int32)
        is_cat = np.array([m.bin_type == binning.BIN_TYPE_CATEGORICAL for m in used])
        # missing_bin: the bin routed by the split's default direction, or -1
        # (mode analysis in ops/split.py docstring)
        mode_a = (nb > 2) & (missing != binning.MISSING_NONE)
        missing_bin = np.where(mode_a & (missing == binning.MISSING_NAN), nb - 1,
                               np.where(mode_a & (missing == binning.MISSING_ZERO),
                                        default_bin, -1)).astype(np.int32)
        self.has_categorical = bool(is_cat.any())
        f = max(len(used), 1)
        # per-feature monotone direction and contri multiplier, mapped from
        # ORIGINAL feature indices to used-feature space (reference:
        # feature_histogram.hpp:1170-1177 FeatureMetainfo init)
        monotone = np.zeros((f,), dtype=np.int8)
        mc = list(config.monotone_constraints or [])
        if mc and len(mc) != self.num_total_features:
            log.fatal(f"monotone_constraints should be the same size as "
                      f"feature number ({self.num_total_features}), "
                      f"got {len(mc)}")
        for i, j in enumerate(self.used_features):
            if j < len(mc):
                monotone[i] = np.int8(mc[j])
        penalty = np.ones((f,), dtype=np.float32)
        fc = list(config.feature_contri or [])
        if fc and len(fc) != self.num_total_features:
            log.fatal(f"feature_contri should be the same size as feature "
                      f"number ({self.num_total_features}), got {len(fc)}")
        for i, j in enumerate(self.used_features):
            if j < len(fc):
                penalty[i] = np.float32(fc[j])
        self._feature_meta = FeatureMeta(
            num_bins=jnp.asarray(nb if len(nb) else np.array([2], np.int32)),
            missing_type=jnp.asarray(missing if len(missing) else np.zeros(1, np.int32)),
            default_bin=jnp.asarray(default_bin if len(default_bin) else np.zeros(1, np.int32)),
            is_categorical=jnp.asarray(is_cat if len(is_cat) else np.zeros(1, bool)),
            monotone=jnp.asarray(monotone),
            penalty=jnp.asarray(penalty),
        )
        self._missing_bin = jnp.asarray(missing_bin if len(missing_bin)
                                        else np.full(1, -1, np.int32))

    # ------------------------------------------------------- helpers
    @property
    def feature_meta(self) -> FeatureMeta:
        self.construct()
        return self._feature_meta

    @property
    def missing_bin(self):
        self.construct()
        return self._missing_bin

    @property
    def bins_T(self):
        """Feature-major [F, N] copy of the bin matrix, built lazily: split
        routing extracts one feature column per split, which on TPU is a
        contiguous slice here vs a strided read of the whole row-major
        matrix (reference keeps per-feature bin arrays natively,
        dense_bin.hpp)."""
        self.construct()
        if getattr(self, "_bins_T", None) is None:
            if getattr(self, "is_pre_partitioned", False):
                # global row-sharded bins: transpose as an SPMD program
                # with an explicit output sharding (every process reaches
                # this property in lockstep during training)
                from jax.sharding import NamedSharding, PartitionSpec as P
                sh = self.bins.sharding
                self._bins_T = jax.jit(
                    lambda b: b.T,
                    out_shardings=NamedSharding(
                        sh.mesh, P(None, sh.spec[0])))(self.bins)
            else:
                self._bins_T = jnp.asarray(self.bins.T)
        return self._bins_T

    def num_used_features(self) -> int:
        """Number of DEVICE COLUMNS (bundles count as one column each)."""
        self.construct()
        if self.bundles is not None:
            return max(len(self.bundles), 1)
        return max(len(self.used_features), 1)

    def bin_new_data(self, X) -> np.ndarray:
        """Bin raw features with this dataset's mappers (prediction path)."""
        self.construct()
        if self.bundles is not None:
            if not _is_scipy_sparse(X):
                X = _to_2d_float(self._pandas_to_codes(X))
            if X.shape[1] != self.num_total_features:
                log.fatal(f"The number of features in data ({X.shape[1]}) is "
                          f"not the same as it was in training data "
                          f"({self.num_total_features}).")
            return self._bin_columns(X)
        if _is_scipy_sparse(X):
            if X.shape[1] != self.num_total_features:
                log.fatal(f"The number of features in data ({X.shape[1]}) is "
                          f"not the same as it was in training data "
                          f"({self.num_total_features}).")
            return self._bin_columns_unbundled(X)
        X = _to_2d_float(self._pandas_to_codes(X))
        if X.shape[1] != self.num_total_features:
            log.fatal(f"The number of features in data ({X.shape[1]}) is not the same"
                      f" as it was in training data ({self.num_total_features}).")
        used = [self.mappers[j] for j in self.used_features]
        Xu = X[:, self.used_features] if len(self.used_features) else np.zeros((len(X), 0))
        return binning.bin_data(Xu, used)

    def create_valid(self, data, label=None, weight=None, group=None,
                     init_score=None, params=None) -> "Dataset":
        return Dataset(data, label=label, reference=self, weight=weight,
                       group=group, init_score=init_score, params=params)

    def subset(self, used_indices: Sequence[int], params=None) -> "Dataset":
        """Row subset sharing this dataset's mappers (reference: basic.py
        Dataset.subset / CopySubrow, dataset.h:416). Requires raw data."""
        if self.data is None:
            log.fatal("Cannot subset a Dataset whose raw data was freed")
        idx = np.asarray(used_indices)
        data = self.data.iloc[idx] if hasattr(self.data, "iloc") else _to_2d_float(self.data)[idx]
        lbl = self.get_label()
        w = self.get_weight()
        return Dataset(data, label=None if lbl is None else lbl[idx],
                       reference=self,
                       weight=None if w is None else w[idx],
                       params=params or self.params)

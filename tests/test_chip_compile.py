"""The default path's Pallas kernels, handed to the TPU's own compiler.

Every other kernel test runs the Pallas interpreter, which accepts what
Mosaic refuses: the kernels of this repo passed all of them while their
row-gather form could not slice an HBM operand below a tile, their split
epilogue asked for ``cumsum``, and a row block of 8192 took five minutes to
schedule. The TPU compiler is installed here and compiles for a chip that
is DESCRIBED, not attached (the `on-chip-measurement` guide, section 2), so
these tests ask it — at the Higgs width the chip smoke runs, F=28, B=255,
42 leaf slots — and guard every later PR at no chip time. A compile that
passes is not a run: chip_smoke.py is the run.

Only one process may describe the topology at a time, and it keeps the
TPU library until it exits. So the topology is described inside a
module-scoped fixture of THIS file (never at import, in a ``skipif`` or in
``parametrize``), every compile runs in the test's own process, and all
such tests live in this one file.
"""

import functools
import re
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from lightgbm_tpu import telemetry
from lightgbm_tpu.models import grower
from lightgbm_tpu.ops import histogram, pallas_hist
from lightgbm_tpu.ops.split import BundleMeta, FeatureMeta, SplitParams

import hlo_text

pytestmark = pytest.mark.pallas

F, B, P, S = 28, 255, 42, 3
N = 1 << 18
RUNG = N // 8             # the deepest default compaction rung


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def _no_persistent_cache():
    """A compile for a described chip is written to jax's persistent cache
    but cannot be read back without the chip; keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture
def as_on_chip(monkeypatch):
    """histogram_tiles picks the kernel only where the backend is a TPU;
    under a described topology jax still reports the CPU. Steer it here,
    in the test, so the dispatch the grower really calls is what gets
    compiled."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def _shapes(one_chip, mode, f, n, m, p=P, block=0):
    sds = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    return dict(
        bins=sds((n, f), jnp.uint8), binsT=sds((f, n), jnp.uint8),
        stats=sds((S, pallas_hist.stat_rows(n, block)),
                  jnp.int8 if mode == "q8" else jnp.float32),
        leaf=sds((n,), jnp.int32), sel=sds((p,), jnp.int32),
        idx=sds((m,), jnp.int32),
        derived=sds((p,), jnp.int32), parent=sds((p, f, B, S), jnp.float32),
        la=sds((2, p, 8), jnp.float32), fm=sds((f, 8), jnp.float32),
        pv=sds((7,), jnp.float32), qs=sds((S,), jnp.float32))


_METHOD = {mode: m for m, mode in histogram._KERNEL_MODE.items()}


def _compile(one_chip, *, mode, epilogue, rung, block, f=F, n=N, m=RUNG):
    """Compile one pass as the grower issues it (ops/histogram.py
    histogram_tiles / histogram_tiles_with_candidates) and return the
    names of the Mosaic kernels in the executable."""
    a = _shapes(one_chip, mode, f, n, m, block=block)
    method = _METHOD[mode]

    def plain(bins, binsT, stats, leaf, sel, idx):
        return histogram.histogram_tiles(
            bins, stats, leaf, sel, B, method=method, block=block,
            binsT=binsT, gather_idx=idx if rung else None)

    def fused(bins, binsT, stats, leaf, sel, idx, derived, parent, la, fm,
              pv, qs):
        return histogram.histogram_tiles_with_candidates(
            bins, stats, leaf, sel, derived, parent, la, fm, pv, B,
            method=method, block=block, binsT=binsT,
            gather_idx=idx if rung else None,
            q_scale=qs if mode == "q8" else None)

    names = ["bins", "binsT", "stats", "leaf", "sel", "idx"]
    if epilogue:
        names += ["derived", "parent", "la", "fm", "pv", "qs"]
    t0 = time.time()
    compiled = jax.jit(fused if epilogue else plain).lower(
        *(a[k] for k in names)).compile()
    text = compiled.as_text()
    kernels = [ln.split("=")[0].strip().lstrip("%")
               for ln in text.splitlines()
               if 'custom_call_target="tpu_custom_call"' in ln]
    print(f"compiled mode={mode} epilogue={epilogue} rung={rung} "
          f"block={block} in {time.time() - t0:.1f} s: {kernels}")
    return kernels


# (mode, epilogue, rung, block): the forms `auto` reaches with default
# parameters on a plain numerical model — the full pass and a compaction
# rung, each with the split epilogue (split_fusion resolves on) and
# without (it resolves off for categorical / EFB / parallel learners) —
# plus q8 (quantized_grad), all at the block the rule gives
# (pallas_hist.DEFAULT_BLOCK), and the ends of what an explicit
# ``hist_block`` is likely to name. The 8192-row q8 cases are the ones
# that ran out of scoped VMEM before the kernel walked its block in chunks.
RULE = pallas_hist.DEFAULT_BLOCK
FORMS = [
    pytest.param("hilo", False, False, RULE, id="full-hilo"),
    pytest.param("q8", False, False, RULE, id="full-q8"),
    pytest.param("hilo", False, True, RULE, id="rung-hilo"),
    pytest.param("hilo", True, False, RULE, id="epilogue-hilo"),
    pytest.param("hilo", True, True, RULE, id="rung-epilogue-hilo"),
    pytest.param("hilo", True, False, 1024, id="epilogue-hilo-blk1024"),
    pytest.param("hilo", True, False, 8192, id="epilogue-hilo-blk8192"),
    pytest.param("q8", True, False, 8192, id="epilogue-q8-blk8192"),
    pytest.param("q8", False, False, 8192, id="full-q8-blk8192"),
]


@pytest.mark.parametrize("mode,epilogue,rung,block", FORMS)
def test_default_path_kernel_compiles(one_chip, as_on_chip, mode, epilogue,
                                      rung, block):
    """One Mosaic kernel of the expected form in the compiled pass, at the
    Higgs width."""
    kernels = _compile(one_chip, mode=mode, epilogue=epilogue, rung=rung,
                       block=block)
    want = (pallas_hist.EPILOGUE_KERNEL_NAME if epilogue
            else pallas_hist.KERNEL_NAME) + "_" + mode
    assert len(kernels) == 1 and kernels[0].startswith(want), kernels


# MS-LTR's width, at the rule's block and at the largest a run has used
# there (PERF.md, PR 29)
@pytest.mark.parametrize("block", [RULE, 8192])
def test_two_group_epilogue_fits_vmem_at_137_features(one_chip, as_on_chip,
                                                      block):
    """The two-group epilogue kernel at 137 features, 255 bins, ``hilo``:
    a plane is 35072 x 128 x 4 = 18.0 MB and the kernel holds the
    accumulator, the parent and the one plane it emits (the last two
    double-buffered), about 90 MB of the 100 MB ``vmem_limit_bytes``. A
    second emitted plane, or a candidate table much wider than the two
    groups', would not compile."""
    kernels = _compile(one_chip, mode="hilo", epilogue=True, rung=False,
                       block=block, f=137)
    assert len(kernels) == 1 and kernels[0].startswith(
        pallas_hist.EPILOGUE_KERNEL_NAME + "_hilo"), kernels


def test_tpu_compile_all_modes(one_chip, as_on_chip):
    """Both kernel forms (full pass, compaction rung) COMPILE for every
    mode at a small production-like shape — the HIGHEST mode included,
    which `deterministic=true` selects."""
    for mode in ("hilo", "highest", "q8"):
        for rung in (False, True):
            kernels = _compile(one_chip, mode=mode, epilogue=False,
                               rung=rung, block=1024, f=8, n=4096, m=2048)
            assert len(kernels) == 1, (mode, rung, kernels)


# (features, params): the Higgs and the MS-LTR forms, q8 and HIGHEST
PLANS = [
    pytest.param(28, {}, "pallas_hilo", id="higgs-hilo-epilogue"),
    pytest.param(137, {}, "pallas_hilo", id="msltr-hilo-epilogue"),
    pytest.param(28, {"quantized_grad": True}, "pallas_q8", id="q8"),
    pytest.param(28, {"deterministic": True}, "pallas", id="highest"),
]


@pytest.mark.parametrize("features,extra,method", PLANS)
def test_the_plan_is_a_pure_function(as_on_chip, features, extra, method):
    """On what looks like a TPU, two boosters over one shape resolve the
    same method, block and tile width, and the numbers are the rule's:
    ``resolve_method``'s kernel, DEFAULT_BLOCK and the structural tile.
    Nothing is timed, so nothing can differ from run to run."""
    import lightgbm_tpu as lgb
    rng = np.random.RandomState(0)
    X = rng.randint(0, 1000, size=(1200, features)).astype(np.float64)
    y = (X[:, 0] > 500).astype(np.float64)
    params = {"objective": "binary", "max_bin": 255, "verbosity": -1,
              **extra}
    plans = []
    for _ in range(2):
        ds = lgb.Dataset(X, label=y, params=params)
        gb = lgb.Booster(params=params, train_set=ds)._boosting
        hm = gb._hist_method()
        st = gb._serial_grow_statics(hm)
        plans.append((hm, st["hist_method"], st["hist_block"],
                      st["tile_leaves"], st["split_fusion"]))
    assert plans[0] == plans[1]
    assert plans[0] == (method, method, pallas_hist.DEFAULT_BLOCK,
                        pallas_hist.structural_tile_leaves(), True)
    assert ds.max_num_bins == 255 and ds.num_used_features() == features
    # and one feature block: the kernel of before there were blocks
    said = gb.hist_plan()
    assert (said["feature_block"], said["feature_blocks"]) == (features, 1)
    assert said["hist_block"] == pallas_hist.DEFAULT_BLOCK


# ------------------------------------------------------- feature blocks

@pytest.mark.parametrize("epilogue", [False, True], ids=["plain", "epilogue"])
def test_the_2000_column_kernel_compiles_inside_vmem(one_chip, as_on_chip,
                                                     epilogue):
    """The benchmark's ``epsilon.train`` width, 2,000 columns at 255 bins,
    through the dispatch the grower calls: the kernel walks the columns in
    feature blocks (pallas_hist.feature_block) and one block's body,
    accumulator, parent planes and candidate tables have to fit the 100 MB
    ``vmem_limit_bytes``. One body unrolled over 2,000 columns would need
    262 MB for its accumulator alone."""
    t0 = time.time()
    kernels = _compile(one_chip, mode="hilo", epilogue=epilogue, rung=False,
                       block=RULE, f=2000)
    want = (pallas_hist.EPILOGUE_KERNEL_NAME if epilogue
            else pallas_hist.KERNEL_NAME) + "_hilo"
    assert len(kernels) == 1 and kernels[0].startswith(want), kernels
    assert pallas_hist.feature_blocks(
        2000, pallas_hist.feature_block(2000, B, "hilo", epilogue)) > 1
    # a body of one feature block: minutes would mean the unroll is back
    assert time.time() - t0 < 300


def test_the_968_column_epilogue_with_missing_values_compiles(one_chip,
                                                              as_on_chip):
    """The benchmark's ``bosch.train`` width, 968 columns at 255 bins,
    every one with a NaN bin: the fused epilogue walks five feature blocks
    of 200, the last with 32 columns of padding whose scan metadata rows
    the wrapper pads (the ``fm`` operand reaches the kernel as data, so
    its NaN rows are no constant the compiler could fold). The plan a
    booster resolves at that width on what looks like a TPU is the cell's
    record, the compaction rungs priced for the cell's 1,000,000 rows."""
    import json
    import os
    import lightgbm_tpu as lgb
    fb = pallas_hist.feature_block(968, B, "hilo", epilogue=True)
    assert (fb, pallas_hist.feature_blocks(968, fb)) == (200, 5)
    t0 = time.time()
    kernels = _compile(one_chip, mode="hilo", epilogue=True, rung=False,
                       block=RULE, f=968)
    assert len(kernels) == 1 and kernels[0].startswith(
        pallas_hist.EPILOGUE_KERNEL_NAME + "_hilo"), kernels
    assert time.time() - t0 < 300

    cell = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmarks", "workloads",
        "bosch.train.json")
    with open(cell) as f:
        want = json.load(f)["plan"]
    rng = np.random.default_rng(0)
    X = np.round(rng.standard_normal((2000, 968)), 2).astype(np.float32)
    X[rng.random((2000, 968)) < 0.8] = np.nan
    params = {"objective": "binary", "max_bin": 255, "verbosity": -1,
              "min_data_in_leaf": 1, "is_enable_sparse": False}
    ds = lgb.Dataset(X, label=(rng.random(2000) < 0.1).astype(np.float32),
                     params=params)
    plan = lgb.Booster(params=params, train_set=ds).hist_plan()
    assert ds.num_used_features() == 968
    assert all(m.missing_type == 2 for m in ds.mappers)
    ladder = histogram.prune_compaction_ladder(
        (125_056, 500_032), "TPU v5 lite", plan["hist_method"], 1_000_000,
        968, 255)
    assert {"feature_block": plan["feature_block"],
            "feature_blocks": plan["feature_blocks"],
            "compaction_ladder": list(ladder),
            "split_fusion": plan["split_fusion"]} == want


# sha256[:16] of the traced pass (its wrapper's operations, the kernel's
# jaxpr, grid and block specs) at the widths the benchmark's cells run,
# (width, epilogue) -> digest, taken by the function below when the
# statistics became channel-major (the kernel's [S, stat_rows] operand);
# the digests of before were taken on the commit before the kernel had
# feature blocks (18b9920), and held until then: at one block a pass is
# the program of before there were blocks, operation for operation
_BEFORE_BLOCKS = {
    (8, False): "e9c9abc704ebbeed", (8, True): "8ded2d52ec23aa33",
    (28, False): "7a1c82b3f2024510", (28, True): "aa073891502eb87d",
    (68, False): "8f1ece47cb93a9f9", (68, True): "b1e858b96e3151be",
    (137, False): "f074f528117ee87d", (137, True): "baec00cdb73de482",
}


def _traced_pass_digest(f, epilogue, n=8192):
    import hashlib
    sds = jax.ShapeDtypeStruct
    args = [sds((f, n), jnp.uint8),
            sds((S, pallas_hist.stat_rows(n)), jnp.float32),
            sds((n,), jnp.int32), sds((P,), jnp.int32)]
    if epilogue:
        args += [sds((P,), jnp.int32), sds((P, f, B, S), jnp.float32),
                 sds((2, P, 8), jnp.float32), sds((f, 8), jnp.float32),
                 sds((7,), jnp.float32)]
        fn = pallas_hist.histogram_tiles_pallas_epilogue
    else:
        fn = pallas_hist.histogram_tiles_pallas_mode
    text = str(jax.make_jaxpr(lambda *a: fn(*a, B))(*args))
    return hashlib.sha256(text.encode()).hexdigest()[:16]


@pytest.mark.parametrize("features,epilogue", sorted(_BEFORE_BLOCKS))
def test_at_one_block_the_pass_is_the_program_of_before(features, epilogue):
    """At 8, 28, 68 and 137 device columns (``expo.train``, ``higgs.train``,
    ``criteo.train-dp4``, ``msltr.train``) the rule gives one block and the
    traced pass equals, text for text, the one the digests were taken
    from: those cells keep their programs. A digest that moves with a jax
    upgrade is taken again; one that moves with an edit to the kernel is
    that edit's to justify."""
    assert pallas_hist.feature_block(features, B, "hilo", epilogue) \
        == features
    assert _traced_pass_digest(features, epilogue) \
        == _BEFORE_BLOCKS[(features, epilogue)]


# ---------------------------------------------------------------- routing

def _grow_text(one_chip, *, split_fusion, with_categorical, n=N):
    """The serial grow program as the Higgs job runs it (255 leaves, the
    rule's block and tile, a feature-major bin matrix), compiled for the
    described chip. Every array is an OPERAND: what the routing may leave
    out has to follow from the statics, not from a constant folded away."""
    N = n                                                     # noqa: N806
    sds = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    per_f = lambda dt: sds((F,), dt)                          # noqa: E731
    meta = FeatureMeta(per_f(jnp.int32), per_f(jnp.int32), per_f(jnp.int32),
                       per_f(jnp.bool_), per_f(jnp.int8),
                       per_f(jnp.float32))
    params = SplitParams(*(sds((), jnp.float32)
                           for _ in SplitParams._fields))
    t0 = time.time()
    text = grower.grow_tree.lower(
        sds((N, F), jnp.uint8), sds((N,), jnp.float32),
        sds((N,), jnp.float32), sds((N,), jnp.float32), meta, params,
        per_f(jnp.float32), per_f(jnp.int32),
        binsT=sds((F, N), jnp.uint8), rng_key=sds((2,), jnp.uint32),
        max_leaves=255, num_bins=B, hist_method="pallas_hilo",
        tile_leaves=pallas_hist.structural_tile_leaves(), hist_block=RULE,
        split_fusion=split_fusion,
        with_categorical=with_categorical).compile().as_text()
    print(f"compiled grow_tree split_fusion={split_fusion} "
          f"categorical={with_categorical} in {time.time() - t0:.1f} s")
    return text


_NOT_EXECUTED = ("parameter", "constant", "get-tuple-element", "tuple",
                 "bitcast")


def _opcode(rest):
    """The opcode in an instruction's text after ``=``: what follows the
    result shape, which ends at the first space outside a tuple's
    parentheses."""
    rest = telemetry._HLO_LAYOUT_RE.sub("", rest)
    depth = 0
    for i, ch in enumerate(rest):
        depth += (ch == "(") - (ch == ")")
        if ch == " " and depth == 0:
            return rest[i + 1:].split("(", 1)[0]
    return ""


def _split_loop_row_passes(text):
    """{instruction: its text, a fusion's fused computation included} for
    what the device executes in the body of the inner split loop (the
    ``while`` that ``apply_splits`` traces under scope ``apply_split``)
    with that scope and a result of N rows: one pass over the rows each.
    Scope and shape are ``telemetry.parse_hlo_scopes``'; which computation
    an instruction sits in is read here, with its expressions."""
    loops = [ln for ln in text.splitlines() if " while(" in ln
             and 'apply_split/while"' in ln]
    assert len(loops) == 1, loops
    body = re.search(r"body=%?([^\s,)}]+)", loops[0]).group(1)
    comps = hlo_text.computations(text)
    _, table = telemetry.parse_hlo_scopes(text)
    out = {}
    for ln in comps[body]:
        _, name, rest = telemetry._HLO_INSTR_RE.match(ln).groups()
        scope, shape = table[name]
        if (scope != "apply_split" or not re.search(rf"\b{N}\b", shape)
                or _opcode(rest) in _NOT_EXECUTED):
            continue
        calls = telemetry._HLO_CALLS_RE.search(rest)
        out[name] = "\n".join([ln] + (comps[calls.group(1)] if calls else []))
    return out


@pytest.mark.parametrize("split_fusion", [True, False],
                         ids=["fused-search", "classic-search"])
def test_a_split_routes_its_rows_in_one_pass(one_chip, as_on_chip,
                                             split_fusion):
    """Without a categorical feature or a bundle a split is the missing /
    threshold test and the select, on the column's own [1, N] layout: one
    fusion over the rows and the loop carry's copy. The fused-search
    program carries ``state.best`` through its loops, so nothing is a
    constant there: with the bitset lookup traced it compiled to six
    N-row instructions a split (a gather, the index relayout it forces,
    a ``select_reduce``), 0.49 s an iteration at Higgs."""
    passes = _split_loop_row_passes(_grow_text(
        one_chip, split_fusion=split_fusion, with_categorical=False))
    print(sorted(passes))
    assert 1 <= len(passes) <= 2, sorted(passes)
    for name, body in passes.items():
        assert "select_reduce" not in name and "gather" not in body, name


def test_a_categorical_split_still_looks_its_bitset_up(one_chip, as_on_chip):
    """With a categorical feature the routing is a conditional on the
    split's kind (PR 41): the loop body holds the ``conditional``, and
    only its branch under scope ``cat_route`` gathers the bitset word by
    the row's bin, so a numerical split of the same step does not."""
    text = _grow_text(one_chip, split_fusion=False, with_categorical=True)
    passes = _split_loop_row_passes(text)
    assert any(" conditional(" in body for body in passes.values()), \
        sorted(passes)
    comps = hlo_text.computations(text)
    _, table = telemetry.parse_hlo_scopes(text)
    cat = []
    for lines in comps.values():
        for ln in lines:
            m = telemetry._HLO_INSTR_RE.match(ln)
            if m and table.get(m.group(2), (None,))[0] == "cat_route":
                calls = telemetry._HLO_CALLS_RE.search(m.group(3))
                cat.append("\n".join(
                    [ln] + (comps.get(calls.group(1), []) if calls else [])))
    assert any("gather" in body for body in cat), len(cat)
    assert not any(table.get(name, (None,))[0] == "cat_route"
                   for name in passes), sorted(passes)


def _statistics_as_made(text, n):
    """The per-row statistics of a grow program of ``n`` rows, compiled:
    (the operand the histogram kernels are handed, the instructions that
    make or move an [n, 3] or [n_pad, 3] float32 array). The grower makes
    them channel-major at the kernels' padded row count once a tree
    (grower.row_stats), so the kernels' constraint reads [3, n_pad] and
    nothing rows-major is made, padded or copied; rows-major, a float32
    [N, 3] is tiled to 128 lanes on the chip, 5.6 GB at 11M rows, and the
    step made it and padded it again every tree."""
    n_pad = pallas_hist.stat_rows(n, RULE)
    operands = re.findall(r'custom_call_target="tpu_custom_call", '
                          r'operand_layout_constraints='
                          r'\{((?:[^{}]|\{[^{}]*\})*)\}', text)
    rows_major = [ln.strip()[:160] for ln in text.splitlines()
                  if re.search(rf"= f32\[({n}|{n_pad}),3\]", ln)]
    return n_pad, operands, rows_major


def test_the_higgs_statistics_reach_the_kernel_as_made(one_chip, as_on_chip):
    """At the Higgs job's own rows (10,500,000: not a whole number of
    4096-row blocks) the fused-search grow program hands the kernel the
    statistics as the gradient phase made them: [3, 10,502,144], and no
    [N, 3] array is made, padded or copied anywhere in it (a pad and a
    copy of one cost 0.16 s an iteration at Higgs, 0.31 at the Expo
    flights)."""
    n = 10_500_000
    n_pad, operands, rows_major = _statistics_as_made(
        _grow_text(one_chip, split_fusion=True, with_categorical=False, n=n),
        n)
    assert n_pad == 10_502_144
    assert operands and all(f"f32[3,{n_pad}]" in o for o in operands), \
        operands
    assert not rows_major, rows_major


# ------------------------------------------------------------ the Expo job

def test_the_expo_grow_program_compiles_and_fits(one_chip, as_on_chip):
    """The grow program of the benchmark's ``expo.train`` cell at its real
    size, for the described chip: 11,000,000 rows, the 8 dense and 3 stream
    device columns the generator's 700 one-hot columns bundle into (the
    streams one concatenation of 1,219,592 entries, the widest of 809,286
    last; the other 410,306 divided between the two narrower streams by
    the shares benchmarks/README-expo.md gives; the job holds the
    library's bundle sample to the
    same rows on every row order), 255 bins, 255 leaves, bundle segments,
    the classic search. It has to compile (the
    stream planes' scatter-add, a stream split's scatter over N rows)
    and to leave room on a 16 GB chip for the step's gradients and the data
    set: the fused step compiled to 11.8 GB when the cell was added, and
    this program, the whole of it but the objective, to 11.80 GB while the
    statistics reached the kernel rows-major: a float32 [N, 3] is tiled to
    128 lanes, 5.6 GB a copy, and the step made a copy and a padded copy
    of it every tree. Channel-major and made at the kernel's row count
    (grower.row_stats), the program compiles to 1.12 GB. The limit is that
    plus 0.1 GB: the statistics by entry are a loop-invariant operand of
    every pass too, and a float32 [E, 3] held rows-major would be 0.62 GB
    where [3, E] takes 20 MB. The routing is a
    conditional on the split's own column: a split on a dense column is
    ONE pass over the rows with nothing of the stream in it, and the
    stream branch scatters without sorting its 809,286 indices first (a
    scatter and a sort on all 254 splits of a tree cost 1.44 of 5.36 s an
    iteration)."""
    n, dense, sp_cols = 11_000_000, 8, (8, 5, 6)
    offsets = (0, 133_072, 410_306, 1_219_592)
    entries, widest = offsets[-1], offsets[-1] - offsets[-2]
    assert widest == 809_286
    g = dense + len(sp_cols)
    sds = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    per_g = lambda dt: sds((g,), dt)                          # noqa: E731
    per_bin = lambda dt: sds((g, B), dt)                      # noqa: E731
    meta = FeatureMeta(per_g(jnp.int32), per_g(jnp.int32), per_g(jnp.int32),
                       per_g(jnp.bool_), per_g(jnp.int8),
                       per_g(jnp.float32))
    bundle = BundleMeta(per_bin(jnp.int32), per_bin(jnp.int32),
                        per_g(jnp.bool_), per_bin(jnp.bool_),
                        per_bin(jnp.bool_), per_bin(jnp.int32),
                        per_bin(jnp.int32))
    params = SplitParams(*(sds((), jnp.float32)
                           for _ in SplitParams._fields))
    t0 = time.time()
    compiled = grower.grow_tree.lower(
        sds((n, dense), jnp.uint8), sds((n,), jnp.float32),
        sds((n,), jnp.float32), sds((n,), jnp.float32), meta, params,
        per_g(jnp.float32), per_g(jnp.int32),
        binsT=sds((dense, n), jnp.uint8), rng_key=sds((2,), jnp.uint32),
        bundle_meta=bundle, sp_cols=sp_cols, sp_offsets=offsets,
        sp_rows=sds((entries,), jnp.int32),
        sp_cell=sds((entries,), jnp.int32),
        sp_default=sds((len(sp_cols),), jnp.int32),
        max_leaves=255, num_bins=B, hist_method="pallas_hilo",
        tile_leaves=pallas_hist.structural_tile_leaves(), hist_block=RULE,
        split_fusion=False, with_categorical=False).compile()
    mem = compiled.memory_analysis()
    total = (mem.temp_size_in_bytes + mem.argument_size_in_bytes
             + mem.output_size_in_bytes - mem.alias_size_in_bytes)
    text = compiled.as_text()
    print(f"compiled the Expo grow program in {time.time() - t0:.1f} s: "
          f"{total:,} bytes")
    assert "hist_tiles_hilo" in text
    # the kernel reads the statistics as the gradient phase made them
    n_pad, operands, rows_major = _statistics_as_made(text, n)
    assert n_pad == 11_001_856
    assert len(operands) == 1 and f"f32[3,{n_pad}]" in operands[0], operands
    assert not rows_major, rows_major
    assert "hist_pass/sparse_hist/" in text
    assert "apply_split/sparse_route/" in text
    assert total < 1.22e9, total
    # the statistics are gathered by entry once a tree, before the loop:
    # the gather's name reads sparse_hist/ without hist_pass/ in front,
    # and trace_scope names an instruction by its innermost scope, so
    # sparse_hist_s_per_iter still counts it. No pass gathers from them
    by_entry = [ln for ln in text.splitlines()
                if re.search(rf"= f32\[3,{entries}\]\S* gather\(", ln)
                and re.search(r'op_name="[^"]*/gather"', ln)]
    assert len(by_entry) == 1, by_entry
    assert 'op_name="jit(grow_tree)/sparse_hist/' in by_entry[0]
    # the bundled search reads its segments' bounds inside its scans
    # (ops/split.segment_prefix_sums): no gather by ELEMENT of the
    # [255, 11, 255, 3] planes, which took 0.51 of the cell's 2.51 s an
    # iteration (a tile's planes taken whole out of the state by their
    # leaf, slices of 11 x 255 x 3, stay)
    by_element = [
        ln for ln in text.splitlines()
        if re.search(rf"= f32\[(255,{g},{B},3|{255 * g * B * 3})\]\S* "
                     r"gather\(.*slice_sizes=\{1(,1)*\}", ln)]
    assert not by_element, by_element
    assert telemetry.parse_hlo_scopes(text)[1][
        by_entry[0].split("=")[0].strip().lstrip("%")][0] == "sparse_hist"
    # a pass gathers the entries' leaf ids from a 16-bit copy of the rows'
    # that the compiler makes, and keeps, in fast memory (S(1)); it left
    # the 44 MB int32 vector in HBM under the same gather: 21.6 against
    # 9.2 ms a pass on the chip
    assert re.search(rf"= s16\[{n}\]\{{[^}}]*S\(1\)\}} convert\(", text)
    # every pass gathers, sorts and scatters over the entries that exist
    in_pass = [ln for ln in text.splitlines()
               if "while/body" in ln and "hist_pass/sparse_hist/" in ln]
    assert any(re.search(rf"= s16\[{entries}\]\S* gather\(", ln)
               for ln in in_pass)
    assert not any(str(len(sp_cols) * widest) in ln for ln in in_pass)
    dense, stream = hlo_text.route_branches(text)
    assert any("apply_split/sparse_route/scatter" in ln for ln in stream)
    assert any(re.search(rf"= s32\[{widest}\]\S* dynamic-slice\(", ln)
               for ln in stream)
    assert not any("sparse_route" in ln for ln in dense)
    assert not any(" sort(" in ln and "sparse_route" in ln
                   for ln in text.splitlines())
    passes = [ln for ln in dense if re.search(rf"= \S*\[1,{n}\]\S* fusion\(",
                                              ln)]
    assert len(passes) == 1, passes

"""chip_smoke.py, rehearsed where there is no chip.

The script itself only runs on a TPU. Its phases are functions of their
sizes and a parameter override, so they run here at a tiny size with the
kernels interpreted: a later PR that breaks an entry point the smoke uses
(lgb.Dataset -> lgb.train -> Booster.predict -> ServeFrontend, or
tree_learner=data over a mesh) fails here before it costs chip time.
"""

import os
import subprocess
import sys

import pytest

import jax

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)            # chip_smoke.py lives there

import chip_smoke  # noqa: E402

# a few thousand rows: below that the held-out AUC of two correct paths
# differs by more than the script's tolerance from sampling noise alone
TINY = chip_smoke.Sizes(rows=3000, valid_rows=1500, warmup_iters=2,
                        timed_iters=2, compare_rows=2000, compare_rounds=3,
                        host_check_rows=128)
OVERRIDES = {"hist_pallas_interpret": True, "num_leaves": 15,
             "min_data_in_leaf": 20, "min_sum_hessian_in_leaf": 1e-3}


@pytest.mark.parametrize("chips", [1, 4])
def test_phases_at_tiny_size(chips, capsys):
    """Every phase of the one-chip smoke — and, for ``--chips 4``, the
    data-parallel run against the serial one over this process's virtual
    devices (tests/conftest.py gives the CPU backend eight) — passes its
    own checks and prints its line."""
    if chips == 1:
        chip_smoke.run_one_chip(TINY, 0, OVERRIDES)
        want = ("datagen:", "construct:", "tune:", "train:", "compare:",
                "predict:", "serve:")
    else:
        X, y, Xv, yv = chip_smoke.make_data(TINY, 0)
        chip_smoke.phase_four_chips(X, y, Xv, yv, TINY, OVERRIDES,
                                    devices=len(jax.devices()))
        want = ("data_parallel:", "serial:", "four_chips:")
    lines = capsys.readouterr().out.splitlines()
    for head in want:
        assert any(ln.startswith(head) for ln in lines), (head, lines)
    assert not any('"ok"' in ln for ln in lines)   # only main() says ok


def test_script_refuses_to_run_without_a_chip():
    """As the driver runs it, but held to the CPU: non-zero exit, and the
    result line never appears."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, os.path.join(REPO, "chip_smoke.py")],
                       capture_output=True, text=True, env=env, cwd=REPO,
                       timeout=300)
    assert r.returncode != 0, r.stdout[-2000:]
    assert '"ok": true' not in r.stdout
    assert "no TPU" in r.stderr

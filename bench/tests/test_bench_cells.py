"""CPU rehearsal of whole cells at tiny sizes: set-up, window, release and
check through ``harness.run_cell`` (interpret mode / XLA twins; the timed
path is the program's own).  The faults and the controls must come out
``correct: false``; the program must come out correct."""
import io
import json
import time

import _paths
import numpy as np
import pytest
from _tiny import tiny

from bench import harness


def run(cell, system=None, seconds=1.0, seed=2**33 + 11):
    out, err = io.StringIO(), io.StringIO()
    res = harness.run_cell(cell, seed=seed, seconds=seconds, trace=False,
                           t_start=time.perf_counter(), system=system,
                           out=out, err=err)
    assert json.loads(out.getvalue().strip().splitlines()[-1]) == res
    # The numbers compared are the last lines on standard error.
    tail = err.getvalue().strip().splitlines()[-len(res["checks"]):]
    assert all(line.startswith("check ") for line in tail)
    return res


CELLS = ["dense16k.closure", "dense16k.paths"]


@pytest.mark.parametrize("name", CELLS)
def test_program_is_correct(name):
    res = run(tiny(name))
    assert res["correct"], res
    assert res["failed"] == 0 and res["attempted"] > 0
    assert set(res["metrics"]) == {m["name"] for m in tiny(name).end_to_end}
    assert res["window_compiles"] == 0
    assert list(res)[-1] == "checks"
    want = {"max_rel_err"} | ({"path_rel_err"} if "paths" in name else set())
    assert set(res["checks"]) == want


def _with_hops(name, dist_of):
    """``w -> closure`` for the cell: ``dist_of(w)``, with the reference's
    next hops where the cell asks for them."""
    from bench import reference

    if "paths" not in name:
        return dist_of
    return lambda w: (dist_of(w), reference.fw_closure(w, successors=True)[1])


@pytest.mark.parametrize("name", CELLS)
def test_closure_state_unchanged_is_caught(name):
    res = run(tiny(name), system=_with_hops(name, lambda w: w))
    assert not res["correct"]
    assert res["checks"]["max_rel_err"]["value"] > 1e-2


@pytest.mark.parametrize("name", CELLS)
def test_closure_answer_altered_is_caught(name):
    from bench import reference

    def altered(w):
        d = reference.fw_closure(w)
        return d.at[:, 3].set(w[:, 3])  # one answer per row altered

    res = run(tiny(name), system=_with_hops(name, altered))
    assert not res["correct"]


@pytest.mark.parametrize("fault", ["self_loop", "direct_edge"])
def test_next_hop_altered_is_caught(fault):
    """Distances right, one next hop per row wrong where it is produced: a
    hop that goes nowhere, or the direct edge in place of a shorter path."""
    from bench import reference

    def altered(w):
        d, s = reference.fw_closure(w, successors=True)
        n = s.shape[0]
        rows = np.arange(n, dtype=s.dtype)
        hop = rows if fault == "self_loop" else np.full(n, 5, s.dtype)
        return d, s.at[:, 5].set(np.where(rows == 5, 5, hop))

    res = run(tiny("dense16k.paths"), system=altered)
    assert not res["correct"]
    assert res["checks"]["max_rel_err"]["value"] <= 1e-4
    assert res["checks"]["path_rel_err"]["value"] > 1e-2


@pytest.mark.parametrize("name", CELLS)
def test_control_is_rejected(name):
    """The reference in bfloat16 in the program's place fails the check
    (kept here at a size a test run holds; PERF.md has the chip readings
    at the cells' own size)."""
    from bench import controls

    cell = tiny(name)
    res = run(cell, system=controls.control_for(cell))
    assert not res["correct"], res["checks"]


def test_no_tpu_exits_nonzero_without_result(tmp_path):
    import os
    import subprocess
    import sys

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(_paths.ROOT / "bench" / "run.py"), "--workload",
         "dense16k.closure", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert "correct" not in p.stdout
    assert "no TPU" in p.stderr


def test_unknown_device_kind_is_refused():
    with pytest.raises(harness.NoChip):
        harness.load_peaks("TPU v99 imaginary")
    assert "vpu_minplus_ops_per_s" in harness.load_peaks("TPU v5 lite")


def test_window_runs_whole_closures():
    """Closures start while fewer than ``seconds`` have passed; the window
    ends when the last one started has finished, and closure_s is the
    whole window over the closures in it."""
    import jax.numpy as jnp

    from bench import reference

    reference.fw_closure(jnp.zeros((128, 128), jnp.float32))  # compiled

    def slow(w):
        time.sleep(0.1)
        return reference.fw_closure(w)

    res = run(tiny("dense16k.closure"), system=slow, seconds=0.25)
    assert res["attempted"] == 3
    assert 0.1 <= res["metrics"]["closure_s"]["value"] < 0.15


@pytest.mark.parametrize("name", CELLS)
def test_traced_run_off_chip_reads_nothing(name):
    """A ``--trace 1`` run traces its whole window; with no TPU in the
    trace every per-layer reader finds nothing and none reads 0."""
    out, err = io.StringIO(), io.StringIO()
    res = harness.run_cell(tiny(name), seed=5, seconds=0.5, trace=True,
                           t_start=time.perf_counter(), out=out, err=err)
    assert res["correct"], res["checks"]
    assert res["metrics"] == {}
    assert list(res)[-1] == "checks"

"""CPU rehearsal of the reachability cell at n=128 (the way ``_tiny`` cuts
the dense cells): the program comes out correct, and the faults and the
half-pivot control come out ``correct: false``."""
import io
import shutil
import time
from pathlib import Path

import _paths  # noqa: F401
import jax.numpy as jnp
import numpy as np
import pytest
from _tiny import tiny

from bench import (
    harness,
    module_trace,
    reach_control,
    reach_reference,
    reach_roofline,
    xplane,
)
from bench.drivers import reach

CELL = "reach16k.packed"


def run(cell, system=None, seconds=1.0, seed=2**33 + 17, trace=False):
    out, err = io.StringIO(), io.StringIO()
    return harness.run_cell(cell, seed=seed, seconds=seconds, trace=trace,
                            t_start=time.perf_counter(), system=system,
                            out=out, err=err)


def test_program_is_correct():
    res = run(tiny(CELL))
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert res["window_compiles"] == 0
    assert set(res["checks"]) == {"reach_mismatch"}
    assert res["checks"]["reach_mismatch"]["value"] == 0.0
    assert set(res["metrics"]) == {"closure_s", "setup_s"}


def _unclosed(edges):
    return reach_reference.pack_lanes(*edges, 128)


def _lane_shifted(edges):
    """The right closure, with lane 0's bits written over lane 1's."""
    words = reach_reference.fw_words(_unclosed(edges), 128)
    return (words & ~2) | ((words & 1) << 1)


@pytest.mark.parametrize("fault", ["unclosed", "lane_shifted", "control"])
def test_faults_and_control_are_caught(fault):
    cell = tiny(CELL)
    system = {"unclosed": _unclosed, "lane_shifted": _lane_shifted,
              "control": reach_control.control_for(cell)}[fault]
    res = run(cell, system=system)
    assert not res["correct"], res["checks"]
    assert res["checks"]["reach_mismatch"]["value"] > 0


def test_traced_run_off_chip_reads_nothing():
    res = run(tiny(CELL), seconds=0.5, trace=True)
    assert res["correct"], res["checks"]
    assert res["metrics"] == {}


def test_kronecker_edges_follow_the_initiator():
    """Graph500's generator at SCALE 10, read through what the random
    labels keep: a self-loop needs both endpoints in quadrant A or D at
    every bit, (A + D)**SCALE of the edges; the vertex labelled 0 before
    the permutation holds (A + B)**SCALE of the out-edges."""
    scale, n = 10, 1024
    src, dst = reach.make_edges(7, n, 4, 16, [0.57, 0.19, 0.19, 0.05])
    m = 16 * n
    assert src.shape == dst.shape == (4, m) and src.dtype == jnp.int32
    src, dst = np.asarray(src), np.asarray(dst)
    assert src.min() >= 0 and src.max() < n and dst.min() >= 0
    loops = (src == dst).mean()
    assert 0.6 * 0.62 ** scale < loops < 1.4 * 0.62 ** scale
    top = max(np.bincount(s, minlength=n).max() for s in src) / m
    assert 0.7 * 0.76 ** scale < top < 1.3 * 0.76 ** scale


# ------------------------------------------------------------ pack readers
DATA = Path(__file__).resolve().parent / "data"
NAMED = DATA / "dense16k_closure_named.xplane.pb"
PACK = ("pack.device_s", "pack_roofline")


def _readings(trace):
    cell = harness.find_cell(harness.load_spec(), CELL)
    return harness.Readings(cell=cell, peaks=harness.load_peaks("TPU v5 lite"),
                            spans=harness.Spans(),
                            window=harness.Window(attempted=1, failed=0),
                            trace=trace)


@pytest.fixture
def kept_trace(tmp_path, monkeypatch):
    """The recorded ``dense16k.closure`` chip trace (3 closures, no
    packer), kept where the tracer keeps this cell's trace."""
    monkeypatch.setattr(harness, "TRACE_DIR", tmp_path)
    d = tmp_path / CELL / "plugins" / "profile" / "1"
    d.mkdir(parents=True)
    shutil.copy(NAMED, d / "host.xplane.pb")
    return xplane.load(NAMED)


def test_every_reader_of_the_cell_reads_nothing_untraced():
    cell = harness.find_cell(harness.load_spec(), CELL)
    assert {"pack.device_s", "pack_roofline", "round.device_s",
            "fw_round_roofline", "closure_mfu"} <= {
                m["name"] for m in cell.per_layer}
    r = _readings(None)
    for m in cell.per_layer:
        assert harness.load_metric(m["name"]).read(r) is None, m["name"]


def test_module_reader_on_a_recorded_chip_trace(kept_trace):
    """The generic module reader gives the solve program the seconds that
    ``program_trace`` gives it, and a program that runs no packer reads
    None for the pack metrics, never 0."""
    r = _readings(kept_trace)
    assert module_trace.device_s(r, "jit_apsp_solve") == pytest.approx(
        15.190042279333335)
    assert all(harness.load_metric(m).read(r) is None for m in PACK)
    moved = xplane.Summary(window=(kept_trace.window[0] + 1,
                                   kept_trace.window[1]),
                           ops=kept_trace.ops, spans=kept_trace.spans)
    assert module_trace.device_s(_readings(moved), "jit_apsp_solve") is None


def test_pack_readers_arithmetic(kept_trace, monkeypatch):
    """Two closures, one 2 ms pack each: 2 ms a closure, and the roofline
    share is one 1 GiB table write plus 64 MiB of edges at 819 GB/s over
    it."""
    window = tuple(kept_trace.window)
    runs = [[(window[0] + 10.0, window[0] + 10.0 + 2e6),
             (window[0] + 5e6, window[0] + 7e6)]]
    monkeypatch.setattr(module_trace, "load",
                        lambda path, module: (window, runs, 2)
                        if module == "jit_apsp_pack" else None)
    r = _readings(kept_trace)
    got = {m: harness.load_metric(m).read(r) for m in PACK}
    n = 16384
    best = reach_roofline.pack_bytes(n, 32, 16 * n) / 819e9
    assert reach_roofline.pack_bytes(n, 32, 16 * n) == 4 * n * n + 8 * 16 * n * 32
    assert got == {"pack.device_s": pytest.approx(2e-3),
                   "pack_roofline": pytest.approx(100 * best / 2e-3)}

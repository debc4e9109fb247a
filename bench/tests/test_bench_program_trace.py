"""The program's own names in a trace (``bench/program_trace.py``): the
solve module, the per-round copies inside its loop and the engine's span.

Hand-built windows for the edges, the trace recorded before the program
named itself (where every reading is None), and a ``dense16k.closure``
trace recorded on a TPU v5e with the names in place."""
import shutil
from pathlib import Path

import _paths  # noqa: F401
import pytest

from bench import harness, program_trace, xplane

DATA = Path(__file__).resolve().parent / "data"
OLD = DATA / "dense16k_closure.xplane.pb"
NAMED = DATA / "dense16k_closure_named.xplane.pb"
METRICS = ("solve.device_s", "round_copy.device_s", "solve.overhead_s")


def _program(**kw):
    base = dict(window=(0.0, 1000.0),
                solves=[[(100.0, 400.0), (500.0, 800.0)]],
                loops=[[(110.0, 390.0), (510.0, 790.0)]],
                copies=[[(120.0, 130.0), (200.0, 210.0), (520.0, 530.0),
                         # once per solve, outside the loop
                         (101.0, 105.0),
                         # an eager copy outside the solve program
                         (50.0, 90.0)]],
                spans=[(90.0, 420.0), (490.0, 805.0)],
                closures=2)
    base.update(kw)
    return program_trace.Program(**base)


def test_module_name():
    assert program_trace.module_name(
        "jit_apsp_solve(10001249987622917784)") == "jit_apsp_solve"
    assert program_trace.module_name("jit_traced(1)") == "jit_traced"


def test_hand_built_readings():
    p = _program()
    assert p.solve_device_s() == pytest.approx(600e-9 / 2)
    # Only the copies inside a while inside the solve module count.
    assert p.round_copy_device_s() == pytest.approx(30e-9 / 2)
    # Spans 330 + 315 ns less the 600 ns of the module inside them.
    assert p.solve_overhead_s() == pytest.approx(45e-9 / 2)


def test_copies_in_a_loop_outside_the_solve_do_not_count():
    p = _program(loops=[[(110.0, 390.0), (850.0, 950.0)]],
                 copies=[[(120.0, 130.0), (860.0, 900.0)]])
    assert p.round_copy_device_s() == pytest.approx(10e-9 / 2)


def test_a_solve_without_copies_reads_zero():
    # The module is there: the copies are gone, and that is a reading.
    assert _program(copies=[[]]).round_copy_device_s() == 0.0


@pytest.mark.parametrize("change", [
    {"closures": 0},
    {"solves": [[]]},
    {"solves": []},
], ids=["no_closures", "no_solve_module", "no_device"])
def test_edges_read_none(change):
    p = _program(**change)
    assert p.solve_device_s() is None
    assert p.round_copy_device_s() is None
    assert p.solve_overhead_s() is None


def test_overhead_needs_the_span():
    p = _program(spans=[])
    assert p.solve_overhead_s() is None
    assert p.solve_device_s() is not None


def _readings(name, trace):
    cell = harness.find_cell(harness.load_spec(), name)
    return harness.Readings(cell=cell, peaks=harness.load_peaks("TPU v5 lite"),
                            spans=harness.Spans(),
                            window=harness.Window(attempted=1, failed=0),
                            trace=trace)


def _read_all(r):
    return {m: harness.load_metric(m).read(r) for m in METRICS}


@pytest.fixture
def trace_dir(tmp_path, monkeypatch):
    monkeypatch.setattr(harness, "TRACE_DIR", tmp_path)
    return tmp_path


def _keep(trace_dir, cell, src):
    d = trace_dir / cell / "plugins" / "profile" / "1"
    d.mkdir(parents=True)
    shutil.copy(src, d / "host.xplane.pb")


def test_no_trace_reads_none(trace_dir):
    r = _readings("dense16k.closure", None)
    assert all(v is None for v in _read_all(r).values())
    # Traced, but the tracer left nothing on disk.
    r = _readings("dense16k.closure", xplane.load(OLD))
    assert all(v is None for v in _read_all(r).values())


def test_trace_before_the_names_reads_none(trace_dir):
    """The trace recorded before the program named its solve: the module is
    ``jit_traced`` and there is no ``apsp.solve`` span."""
    _keep(trace_dir, "dense16k.closure", OLD)
    r = _readings("dense16k.closure", xplane.load(OLD))
    assert all(v is None for v in _read_all(r).values())
    p = program_trace.load(OLD)
    assert p.closures == 3 and not any(p.solves) and not p.spans


def test_another_runs_trace_is_not_read(trace_dir):
    _keep(trace_dir, "dense16k.closure", NAMED)
    s = xplane.load(NAMED)
    moved = xplane.Summary(window=(s.window[0] + 1, s.window[1]), ops=s.ops,
                           spans=s.spans)
    assert all(v is None for v in
               _read_all(_readings("dense16k.closure", moved)).values())


def test_recorded_named_trace(trace_dir):
    """A ``--trace 1`` run of ``dense16k.closure`` on a TPU v5e, 3 closures
    (the readings its own run printed)."""
    _keep(trace_dir, "dense16k.closure", NAMED)
    s = xplane.load(NAMED)
    got = _read_all(_readings("dense16k.closure", s))
    assert got == {"solve.device_s": pytest.approx(15.190042279333335),
                   "round_copy.device_s": pytest.approx(0.4164039286666667),
                   "solve.overhead_s": pytest.approx(0.011773981)}
    p = program_trace.load(NAMED)
    # One whole-table copy a round, 128 rounds a closure, each inside the
    # loop; the 12 copies outside it are not counted.
    inside = [c for c in p.copies[0]
              if program_trace._inside(*c, p.loops[0])]
    assert len(p.loops[0]) == p.closures == 3
    assert len(inside) == 128 * 3 and len(p.copies[0]) == 396
    # The round kernel runs inside the solve program, and the solve
    # program with the engine's work around it inside the closure.
    closures = s.span_list("bench.closure")
    closure_s = sum(b - a for a, b in closures) / len(closures) * 1e-9
    assert s.kernel_s(["fw_round"]) / 3 < got["solve.device_s"]
    assert got["solve.device_s"] + got["solve.overhead_s"] < closure_s

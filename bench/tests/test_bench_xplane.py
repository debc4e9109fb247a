"""Trace reduction: synthetic intervals, and a trace the harness recorded
on a TPU v5e (``data/``: a ``--trace 1`` run of ``dense16k.closure``,
checked by hand for its kernel event names)."""
from pathlib import Path

import _paths  # noqa: F401
import pytest

from bench import harness, xplane

DATA = Path(__file__).resolve().parent / "data"


def test_op_family():
    assert xplane.op_family(
        "%fw_round.7 = f32[16384,16384]{1,0:T(8,128)} custom-call(s32[2] "
        "%a.1), custom_call_target=\"tpu_custom_call\"") == "fw_round"
    assert xplane.op_family("%fw_round_with_successors.12 = (f32[1]) x") == \
        "fw_round_with_successors"
    assert xplane.op_family("%copy.11 = f32[4] copy(f32[4] %x)") == "copy"
    assert xplane.op_family("%while = (s32[]) while(...)") == "while"
    assert xplane.op_family("%add_select_fusion = f32[] fusion()") == \
        "add_select_fusion"


def test_busy_idle_and_spans_synthetic():
    s = xplane.Summary(
        window=(0.0, 100.0),
        ops=[[("while", 10.0, 60.0), ("fw_round", 10.0, 30.0),
              ("fw_round", 30.0, 50.0), ("copy", 70.0, 80.0)]],
        spans=[("bench.traced", 0.0, 100.0), ("bench.closure", 5.0, 55.0),
               ("bench.take", 60.0, 70.0), ("bench.take", 80.0, 100.0)])
    assert s.busy_s() == pytest.approx(60e-9)      # [10,60] + [70,80]
    assert s.window_s() == pytest.approx(100e-9)
    assert s.kernel_s(["fw_round"]) == pytest.approx(40e-9)
    assert s.kernel_count(["fw_round"]) == 2
    assert s.top_ops()[0] == ["fw_round", pytest.approx(40e-9)]
    assert all(f != "while" for f, _ in s.top_ops())
    gaps = s.idle_gaps()
    assert [g[0] for g in gaps] == ["bench.take", "bench.closure",
                                    "bench.take"]
    assert gaps[0][1] == pytest.approx(20e-9)


def _recorded():
    found = sorted(DATA.glob("*.xplane.pb"))
    if not found:
        pytest.fail("the recorded trace is missing from bench/tests/data")
    return xplane.load(found[0])


def test_recorded_trace_kernels_and_spans():
    s = _recorded()
    closures = s.span_list("bench.closure")
    assert closures, "the harness's closure spans are in the trace"
    # one fused round per pivot round: n / 128 per closure at n=16384
    assert s.kernel_count(["fw_round"]) == 128 * len(closures)
    assert 0 < s.kernel_s(["fw_round"]) <= s.busy_s() <= s.window_s()
    top = s.top_ops()
    assert top[0][0] == "fw_round" and len(top) <= 10
    assert len(s.idle_gaps()) <= 10


def test_recorded_trace_metrics():
    s = _recorded()
    cell = harness.find_cell(harness.load_spec(), "dense16k.closure")
    r = harness.Readings(cell=cell, peaks=harness.load_peaks("TPU v5 lite"),
                         spans=harness.Spans(),
                         window=harness.Window(attempted=1, failed=0),
                         trace=s)
    got = {m["name"]: harness.load_metric(m["name"]).read(r)
           for m in cell.per_layer}
    assert got["round.device_s"] == pytest.approx(
        s.kernel_s(["fw_round"]) / len(s.span_list("bench.closure")))
    assert 0 < got["fw_round_roofline"] < 100
    assert 0 <= got["idle_share.closure"] < 100
    # The whole closure's share bounds its kernel's.
    assert 0 < got["closure_mfu"] <= got["fw_round_roofline"]

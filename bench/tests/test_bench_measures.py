"""How the harness measures: the per-layer readers, the roofline
arithmetic, and discovery of new cells' files."""
import json

import _paths  # noqa: F401
import pytest

from bench import harness, roofline, xplane


def _readings(cell, **kw):
    win = harness.Window(attempted=1, failed=0)
    return harness.Readings(cell=cell, peaks=kw.pop("peaks", {}),
                            spans=kw.pop("spans", harness.Spans()),
                            window=win, trace=kw.pop("trace", None))


@pytest.mark.parametrize("name", ["dense16k.closure", "dense16k.paths"])
def test_readers_return_nothing_without_readings(name):
    cell = harness.find_cell(harness.load_spec(), name)
    r = _readings(cell)
    for m in cell.per_layer:
        assert harness.load_metric(m["name"]).read(r) is None, m["name"]


def test_closure_roofline_counts_the_problem():
    peaks = {"vpu_minplus_ops_per_s": 2e12, "hbm_bytes_per_s": 1e12}
    assert roofline.closure_ops(1024) == 2 * 1024 ** 3
    assert roofline.closure_bytes(1024, word=8) == 16 * 1024 ** 2
    # VPU-bound: 2 n^3 / 2e12 = 1.07 ms beats 8 MiB / 1 TB/s = 8.4 us
    assert roofline.closure_roofline_s(1024, peaks) == pytest.approx(
        2 * 1024 ** 3 / 2e12)
    # HBM-bound when the bandwidth is tiny
    slow = dict(peaks, hbm_bytes_per_s=1.0)
    assert roofline.closure_roofline_s(4, slow) == 2 * 4 * 16


def _trace(kernel, busy_ns, closures, window_ns):
    per = window_ns / closures
    return xplane.Summary(
        window=(0.0, window_ns),
        ops=[[(kernel, i * per, i * per + busy_ns / closures)
              for i in range(closures)]],
        spans=[("bench.closure", i * per, (i + 1) * per)
               for i in range(closures)])


@pytest.mark.parametrize("name,kernel,reader", [
    ("dense16k.closure", "fw_round", "fw_round_roofline"),
    ("dense16k.paths", "fw_round_with_successors",
     "fw_round_with_successors_roofline")])
def test_roofline_readers(name, kernel, reader):
    cell = harness.find_cell(harness.load_spec(), name)
    cell.config = dict(cell.config, n=1024)
    peaks = {"vpu_minplus_ops_per_s": 2e12, "hbm_bytes_per_s": 1e12}
    best = 2 * 1024 ** 3 / 2e12
    # 2 closures, each 4x the roofline on the kernel, in 5x the roofline
    trace = _trace(kernel, 8 * best * 1e9, 2, 10 * best * 1e9)
    r = _readings(cell, peaks=peaks, trace=trace)
    got = {m["name"]: harness.load_metric(m["name"]).read(r)
           for m in cell.per_layer}
    assert got[reader] == pytest.approx(25.0)
    assert got["closure_mfu"] == pytest.approx(20.0)
    assert got["idle_share.closure"] == pytest.approx(20.0)
    # The other cell's kernel is not in this trace: its readers say nothing.
    other = [m for m in ("round.device_s", "succ_round.device_s")
             if m not in got]
    assert other and all(harness.load_metric(m).read(r) is None
                         for m in other)


def _write(root, rel, text):
    p = root / rel
    p.parent.mkdir(parents=True, exist_ok=True)
    p.write_text(text)


def test_new_config_traffic_and_metric_are_found_by_name(tmp_path):
    """A later change adds a cell by adding files: nothing else is edited."""
    spec = harness.load_spec()
    spec["configs"].append({"name": "dense2k", "source": "x",
                            "file": "bench/configs/dense2k.json",
                            "reduced": [], "why": "x"})
    spec["workloads"].append({"name": "dense2k.burst", "config": "dense2k",
                              "traffic": "burst", "chips": 1, "why": "x"})
    spec["end_to_end"][0]["workloads"].append("dense2k.burst")
    spec["per_layer"].append({"name": "new.share", "unit": "%",
                              "better": "higher", "source": "device_trace",
                              "layer": "kernels", "moves": "closure_s",
                              "workloads": ["dense2k.burst"]})
    _write(tmp_path, "BENCHMARK.json", json.dumps(spec))
    _write(tmp_path, "bench/configs/dense2k.json", '{"n": 2048}')
    _write(tmp_path, "bench/traffic/burst.json", '{"kind": "closure"}')
    _write(tmp_path, "bench/metrics/new.share.py",
           "def read(r):\n    return 42.0\n")
    cell = harness.find_cell(harness.load_spec(tmp_path), "dense2k.burst",
                             root=tmp_path)
    assert cell.config == {"n": 2048}
    assert cell.traffic == {"kind": "closure"}
    assert [m["name"] for m in cell.end_to_end] == ["closure_s", "setup_s"]
    assert [m["name"] for m in cell.per_layer] == ["new.share"]
    assert harness.load_driver(cell).__name__ == "bench.drivers.closure"
    assert harness.load_metric("new.share", root=tmp_path).read(None) == 42.0


def test_every_listed_metric_has_a_reader():
    spec = harness.load_spec()
    for m in spec["per_layer"]:
        assert callable(harness.load_metric(m["name"]).read)
    for w in spec["workloads"]:
        cell = harness.find_cell(spec, w["name"])
        assert any(m["name"] == "setup_s" for m in cell.end_to_end)
        assert len(cell.end_to_end) >= 2 and cell.per_layer


def test_result_line_puts_the_checks_last():
    line = harness.result_line(
        correct=True, attempted=3, failed=0,
        metrics={"closure_s": {"value": 1.5, "unit": "s"}},
        device={"platform": "tpu"}, checks=[harness.Check("gap", 0.0, 1.0)],
        breakdown={"device_ops": [], "idle_gaps": []})
    out = json.loads(line)
    assert list(out)[:5] == ["correct", "attempted", "failed", "metrics",
                             "device"]
    assert list(out)[-1] == "checks"
    assert out["checks"] == {"gap": {"value": 0.0, "limit": 1.0}}

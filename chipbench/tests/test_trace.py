"""The reduction from trace to per-layer metrics, on a trace recorded on
the chip (``chipbench/data/*.events.json.gz``, extracted from the profiler's
``.xplane.pb`` by ``reduce_trace.extract``) and on hand-made events."""
import gzip
import json
import pathlib

import pytest

import tiny
import bench
import reduce_trace as rt

DATA = pathlib.Path(__file__).resolve().parents[1] / "data"
RECORDED = sorted(DATA.glob("*.events.json.gz"))


def brute_busy(ops, lo, hi):
    """Busy nanoseconds by walking every nanosecond boundary."""
    points = sorted({lo, hi} | {min(max(t, lo), hi) for _, s, e, *_ in ops
                                for t in (s, e)})
    busy = 0
    for a, b in zip(points, points[1:]):
        mid = (a + b) / 2
        if any(s <= mid < e for _, s, e, *_ in ops):
            busy += b - a
    return busy


def test_self_time_of_nested_ops():
    ops = [["while", 0, 100], ["fusion.1", 10, 30], ["coded_encode.3", 40, 50],
           ["all-gather.6", 120, 150], ["psum.7", 150, 160]]
    st = rt.self_times(ops, 0, 200)
    assert st == {"while": 70, "fusion.1": 20, "coded_encode.3": 10,
                  "all-gather.6": 30, "psum.7": 10}
    assert rt.union_ns([(s, e) for _, s, e in ops], 0, 200) == 140
    assert [rt.kind_of(n) for n, *_ in ops] == [
        "compute", "compute", "encode", "collective", "collective"]


def test_reduce_hand_made_steps():
    ext = {"window": [0, 1000], "host": [["step", 0, 1000],
                                         ["place", 400, 520]],
           "chips": {"0": {"ops": [["fusion", 0, 400, ""],
                                   ["coded_decode.1", 400, 450, ""],
                                   ["fusion", 500, 900, ""]],
                           "modules": [["jit_stepfn", 0, 450],
                                       ["jit_stepfn", 500, 900]]}}}
    r = rt.reduce(ext, steps=2)
    (c,) = r.chips
    assert c.busy_s == pytest.approx(850e-9)
    assert c.by_kind["decode"] == pytest.approx(50e-9)
    assert c.step_gaps_s == [pytest.approx(50e-9)]
    b = rt.breakdown(r)
    # the longest gap, after the last op, lies under the step alone; the
    # one between the programs also under "place", the innermost event
    assert b["idle_gaps"] == [["step", pytest.approx(100e-9)],
                              ["place", pytest.approx(50e-9)]]


@pytest.mark.parametrize("path", RECORDED, ids=lambda p: p.name)
def test_recorded_chip_trace(path):
    ext = json.loads(gzip.decompress(path.read_bytes()))
    lo, hi = ext["window"]
    r = rt.reduce(ext, steps=ext["steps"])
    assert r.chips and r.window_s > 0
    for key, c in zip(sorted(ext["chips"], key=int), r.chips):
        ops = ext["chips"][key]["ops"]
        assert c.busy_s == pytest.approx(brute_busy(ops, lo, hi) / 1e9)
        # self time partitions the busy time
        assert sum(c.by_op.values()) == pytest.approx(c.busy_s, rel=1e-9)
        assert c.by_kind["encode"] > 0 and c.by_kind["decode"] > 0
    cell = tiny.full_cell(ext["workload"])
    peaks = json.loads((bench.HERE / "peaks.json").read_text())
    ctx = bench.ReadContext(r, cell, peaks[ext["device_kind"]])
    got = {m["name"]: bench.read_metric(m["name"], ctx)
           for m in cell.per_layer}
    for name in ("mfu", "encode_roofline", "decode_roofline",
                 "device_idle_share"):
        assert 0 < got[name] <= 100, (name, got[name])
    assert got == pytest.approx(ext["metrics"])

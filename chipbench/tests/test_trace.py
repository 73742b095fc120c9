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


def scoped():
    """Two chips, two steps; ops with named scopes of two layers, one op
    with none, and a kernel of a model's own inside a scope."""
    ops = [["while.1", 0, 300, "", "coded.grad"],
           ["fusion.2", 10, 60, "", "moe.route"],
           ["expert_mm.3", 60, 160, "", "moe.experts"],
           ["fusion.4", 300, 340, "", ""],
           ["coded_encode.5", 340, 400, "", "coded.encode"]]
    chip = {"ops": ops, "modules": [["jit_stepfn", 0, 400]]}
    other = {"ops": [op[:3] + op[3:4] + ["moe.experts" if i == 2 else op[4]]
                     for i, op in enumerate(ops)],
             "modules": chip["modules"]}
    other["ops"][2][2] = 260                # a slower kernel on chip 1
    return {"window": [0, 1000], "host": [], "chips": {"0": chip,
                                                       "1": other}}


def test_time_by_scope_and_a_models_kernel():
    r = rt.reduce(scoped(), steps=2)
    c0, c1 = r.chips
    # self time: the loop's own part, its children under their scopes
    assert c0.by_scope == {"coded.grad": pytest.approx(150e-9),
                           "moe.route": pytest.approx(50e-9),
                           "moe.experts": pytest.approx(100e-9),
                           "coded.encode": pytest.approx(60e-9)}
    assert c1.by_scope["moe.experts"] == pytest.approx(200e-9)
    # time by scope leaves out ops with none: the rest of the busy time
    for c in r.chips:
        assert sum(c.by_scope.values()) + 40e-9 == pytest.approx(c.busy_s)
    assert c0.by_kind["encode"] == pytest.approx(60e-9)
    cell = bench.load_cell(tiny.CELLS[0])
    peak = {"hbm_bytes_per_s": 1e12, "bf16_flops_per_s": 1e15}
    ctx = bench.ReadContext(r, cell, peak)
    # ms a step, mean over chips: a scope, and a layer's phases together
    assert ctx.time_in_scope("moe.experts") == pytest.approx(
        1e3 * (100e-9 + 200e-9) / 2 / 2)
    assert ctx.time_in_scope("moe") == pytest.approx(
        1e3 * (150e-9 + 250e-9) / 2 / 2)
    assert ctx.time_in_scope("moe.gate") is None
    assert ctx.time_in_scope("attn") is None
    # a kernel the model declares: its bytes at 1e12 B/s take 50 ns a step
    assert ctx.roofline_of("expert_mm") is None     # not declared
    ctx.model_kernels = {"expert_mm": (r"^expert_mm\.", 50e3, 1.0)}
    assert ctx.roofline_of("expert_mm") == pytest.approx(
        100 * (100e-9 / 100e-9 + 100e-9 / 200e-9) / 2)
    ctx.model_kernels = {"expert_mm": (r"^no_such_op", 50e3, 1.0)}
    assert ctx.roofline_of("expert_mm") is None


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
        assert c.by_scope == {}         # recorded before ops kept scopes
    cell = bench.load_cell(ext["workload"])
    peaks = json.loads((bench.HERE / "peaks.json").read_text())
    ctx = bench.ReadContext(r, cell, peaks[ext["device_kind"]])
    got = {m["name"]: bench.read_metric(m["name"], ctx)
           for m in cell.per_layer}
    for name in ("mfu", "encode_roofline", "decode_roofline",
                 "device_idle_share"):
        assert 0 < got[name] <= 100, (name, got[name])
    assert got == pytest.approx(ext["metrics"])

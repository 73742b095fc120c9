"""Host spans and named phases (``phases.py``) on hand-made events and on
a trace recorded on the chip with the spans and scopes in it
(``chipbench/data/spans/*.events.json.gz``, written by ``phases.py
--record`` from a trace that ``run.py --keep-trace`` kept)."""
import gzip
import json
import pathlib

import pytest

import tiny  # noqa: F401  (puts the benchmark on the path)
import phases
import reduce_trace as rt

RECORDED = sorted((pathlib.Path(__file__).resolve().parents[1] / "data"
                   / "spans").glob("*.events.json.gz"))


def test_innermost_scope_of_a_path():
    assert rt.scope_of("jit(stepfn)/shard_map/while/body/coded.grad/"
                       "transpose(jvp(dot_general))") == "coded.grad"
    # an all-gather issued by the decode sits under both scopes
    assert rt.scope_of("jit(stepfn)/coded.decode/coded.exchange/"
                       "all_gather") == "coded.exchange"
    assert rt.scope_of("jit(stepfn)/add") == ""
    # any layer's scope, also inside a transform of the backward pass
    assert rt.scope_of("jit(stepfn)/coded.grad/transpose(jvp(moe.route))/"
                       "dot_general") == "moe.route"
    assert rt.scope_of("jit(stepfn)/coded.grad/jvp(moe_2.up_proj)") \
        == "moe_2.up_proj"


def _varint(n):
    out = b""
    while True:
        out += bytes([(n & 0x7F) | (0x80 if n > 0x7F else 0)])
        n >>= 7
        if not n:
            return out


def _field(number, value):
    """One protobuf field: an int as a varint, bytes or str length-delimited."""
    if isinstance(value, int):
        return _varint(number << 3) + _varint(value)
    data = value.encode() if isinstance(value, str) else value
    return _varint(number << 3 | 2) + _varint(len(data)) + data


def test_op_scopes_read_from_the_xplane_proto(tmp_path):
    """Each device op's scope comes from its metadata's ``tf_op`` stat,
    given as a string or as a reference to an interned stat name."""
    def stat_meta(key, name):
        return _field(5, _field(1, key) + _field(2, _field(1, key)
                                                   + _field(2, name)))

    def event_meta(key, name, stats):
        body = _field(1, key) + _field(2, name) + b"".join(
            _field(5, st) for st in stats)
        return _field(4, _field(1, key) + _field(2, body))

    path = "jit(stepfn)/coded.decode/coded.exchange/all-gather"
    tpu = (_field(2, "/device:TPU:0") + stat_meta(1, "tf_op")
           + stat_meta(2, "hlo_category") + stat_meta(3, path)
           # a line (field 3) and a fixed-width stat are skipped
           + _field(3, _field(2, "XLA Ops"))
           + event_meta(7, "%fusion.1 = f32[8]", [
               _field(1, 2) + _field(5, "loop fusion"),
               _field(1, 1) + _field(5, "jit(stepfn)/coded.grad/mul")])
           + event_meta(8, "%all-gather.2 = f32[8]", [
               _field(1, 1) + _field(7, 3),
               _field(1, 2) + b"\x11" + bytes(8)])
           + event_meta(9, "%copy.3 = f32[8]", []))
    host = _field(2, "/host:CPU") + event_meta(1, "trainer.sync", [])
    xplane = tmp_path / "t.xplane.pb"
    xplane.write_bytes(_field(1, tpu) + _field(1, host))
    assert rt.op_scopes(str(xplane)) == {"0": {
        "%fusion.1 = f32[8]": "coded.grad",
        "%all-gather.2 = f32[8]": "coded.exchange",
        "%copy.3 = f32[8]": ""}}


def hand_made():
    """Two chips, three steps of one program; host spans around them."""
    host = [["train", 0, 1000], ["trainer.dispatch", 0, 5],
            ["trainer.sync", 50, 310], ["trainer.readback", 310, 335],
            ["trainer.inputs", 335, 345], ["trainer.dispatch", 345, 360],
            ["trainer.sync", 380, 620], ["trainer.readback", 620, 630],
            ["trainer.inputs", 630, 660], ["trainer.inputs", 655, 672],
            ["trainer.dispatch", 672, 705], ["trainer.sync", 705, 960]]
    ops = [["fusion.1", 0, 200, "", "coded.grad"],
           ["fusion.2", 200, 300, "", "coded.apply"],
           # a gather under decode and exchange: the innermost one counts
           ["all-gather.3", 350, 390, "", "coded.exchange"],
           ["while.4", 390, 600, "", "coded.grad"],
           ["fusion.5", 400, 450, "", "coded.encode"],
           ["fusion.6", 700, 900, "", ""]]
    modules = [["jit_stepfn", 0, 300], ["jit_stepfn", 350, 600],
               ["jit_slice", 305, 306], ["jit_stepfn", 700, 900]]
    chip = {"ops": ops, "modules": modules}
    return {"window": [0, 1000], "host": host,
            "chips": {"0": chip, "1": json.loads(json.dumps(chip))}}


def test_self_time_by_scope():
    (c, _) = phases.reduce(hand_made())
    assert c.by_scope == {"coded.grad": pytest.approx(360e-9),
                          "coded.apply": pytest.approx(100e-9),
                          "coded.exchange": pytest.approx(40e-9),
                          "coded.encode": pytest.approx(50e-9),
                          "": pytest.approx(200e-9)}
    # self time by scope partitions what reduce_trace calls busy
    r = rt.reduce(hand_made(), steps=3)
    assert sum(c.by_scope.values()) == pytest.approx(r.chips[0].busy_s)


def test_gap_cover_by_span():
    (c, _) = phases.reduce(hand_made())
    # the gaps between the step program's runs, not its idle time: the
    # small program at 305 lies inside the first
    assert c.gaps == [(300, 350), (600, 700)]
    assert c.gap_spans["trainer.sync"] == [10, 20]
    assert c.gap_spans["trainer.readback"] == [25, 10]
    # two overlapping inputs events count once
    assert c.gap_spans["trainer.inputs"] == [10, 42]
    assert c.gap_spans["trainer.dispatch"] == [5, 28]
    assert "trainer.checkpoint" not in c.gap_spans     # not in the trace
    m = phases.metrics(phases.reduce(hand_made()), steps=3)
    assert m == pytest.approx({"fwd_bwd_ms": 360e-6 / 3, "apply_ms": 100e-6 / 3,
                               "gap_sync_ms": 15e-6, "gap_readback_ms": 17.5e-6,
                               "gap_inputs_ms": 26e-6,
                               "gap_dispatch_ms": 16.5e-6})


def test_idle_gaps_named_by_the_span_covering_most():
    r = rt.reduce(hand_made(), steps=3)
    # the window's tail after the last op lies under trainer.sync (60 of
    # 100 ns) and under the outer "train", which the old rule would name
    assert phases.idle_gaps(r) == [["trainer.inputs", pytest.approx(100e-9)],
                                   ["trainer.sync", pytest.approx(100e-9)],
                                   ["trainer.readback", pytest.approx(50e-9)]]
    # a gap no span covers keeps the old rule
    bare = dict(hand_made(), host=[["train", 0, 1000]])
    assert all(n == "train" for n, _ in
               phases.idle_gaps(rt.reduce(bare, steps=3)))


def test_clock_disagreements_found():
    ext = hand_made()
    assert phases.clock_disagreements(ext) == []
    ext["host"] = [e if e[1] != 672 else ["trainer.dispatch", 705, 710]
                   for e in ext["host"]]
    bad = phases.clock_disagreements(ext)
    assert len(bad) == 2 and "before its dispatch" in bad[0]


@pytest.mark.parametrize("path", RECORDED, ids=lambda p: p.name)
def test_recorded_spans_and_phases(path):
    """On a chip trace the four spans cover most of each gap between step
    programs and no more than the gap, the two phases fit in what
    ``reduce_trace`` calls compute, every step program ends before its
    ``trainer.sync`` span does, and the reduction gives what it gave on
    the chip.  The other half of the clock check does not hold there:
    each step program of the recorded 8b trace starts 0.62-0.83 ms before
    its ``trainer.dispatch`` span (PERF.md, section 7), so those
    disagreements are compared with the recorded ones, not asserted
    away."""
    ext = json.loads(gzip.decompress(path.read_bytes()))
    got = phases.summary(ext, ext["steps"])
    m = got["metrics"]
    assert set(m) == set(phases.BY_SCOPE) | set(phases.BY_SPAN)
    spans = sum(m[n] for n in phases.BY_SPAN)
    assert 0.85 * got["step_gap_ms"] <= spans
    assert spans <= got["step_gap_ms"] * (1 + 1e-9)
    assert m["fwd_bwd_ms"] + m["apply_ms"] <= got["compute_ms"] + 0.5
    bad = got["clock_disagreements"]
    assert not [b for b in bad if "after its sync" in b]
    assert bad == ext["summary"]["clock_disagreements"]
    assert m == pytest.approx(ext["summary"]["metrics"])

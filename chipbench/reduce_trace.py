"""From a profiler trace to the numbers the per-layer metrics read.

Two stages, so that the second can be checked on a trace kept in the
repository:

``extract(xplane_path)`` reads JAX's ``.xplane.pb`` with
``jax.profiler.ProfileData`` and keeps, per TPU chip, the device
operations (line "XLA Ops") and program executions (line "XLA Modules"),
and the host's events, as plain lists ``[name, start_ns, end_ns]`` on the
profiler's one clock, plus the benchmark's window annotation.  A device
op also keeps its HLO category and its named scope: the innermost part of
its name path of the form ``<layer>.<phase>`` (``jax.named_scope``, such
as ``coded.grad``), or ``""`` where it has none.

``reduce(extracted, steps)`` attributes each chip's time inside the window
to the innermost operation running (self time, so an operation that
encloses others, such as a loop, counts only its own part) and sums it by
kind: the codec kernels (by the name XLA gives their custom calls,
``coded_encode*`` and ``coded_decode*``), collectives (by HLO opcode or
name), and everything else ("compute"); and by named scope.  Busy time is
the union of the operations' intervals; the rest of the window is idle.
"""
from __future__ import annotations

import dataclasses
import pathlib
import re

WINDOW = "chipbench.window"
KERNELS = {"encode": re.compile(r"coded_encode"),
           "decode": re.compile(r"coded_decode")}
COLLECTIVE = re.compile(
    r"all-gather|all-reduce|all-to-all|reduce-scatter|collective-permute|"
    r"all_gather|all_reduce|all_to_all|psum|ppermute|send|recv")
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
# a named scope in an op's name path: a whole word <layer>.<phase>, also
# inside a transform's parentheses, as in "transpose(jvp(coded.grad))"
SCOPE = re.compile(r"(?<![\w.])[A-Za-z][A-Za-z0-9_]*\.[A-Za-z_][A-Za-z0-9_]*")
PATH_STAT = "tf_op"


# ------------------------------------------------------------------ extract
def _stat(ev, key):
    for k, v in ev.stats:
        if k == key:
            return v
    return None


def scope_of(path: str) -> str:
    """The innermost ``<layer>.<phase>`` part of an op's name path, or
    ``""``."""
    found = SCOPE.findall(path)
    return found[-1] if found else ""


# ``jax.profiler.ProfileData`` gives an event its own stats only; an op's
# name path is a stat of the op's metadata, which all its events share.  So
# the device planes' op metadata is read from the ``.xplane.pb`` here, with
# the few fields of the XPlane proto that hold it (tsl's xplane.proto:
# XSpace.planes 1; XPlane.name 2, .event_metadata 4, .stat_metadata 5; a map
# entry's key 1 and value 2; XEventMetadata.name 2, .stats 5;
# XStatMetadata.name 2; XStat.metadata_id 1, .str_value 5, .ref_value 7).
def _varint(buf, i: int) -> tuple[int, int]:
    shift = value = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """(field number, value) of a protobuf message: varints as ints,
    length-delimited fields as memoryviews; fixed-width fields skipped."""
    i = 0
    while i < len(buf):
        key, i = _varint(buf, i)
        field, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            i += 8 if wire == 1 else 4
            continue
        else:
            raise ValueError(f"protobuf wire type {wire} at byte {i}")
        yield field, value


def _entry(buf) -> tuple[int, memoryview]:
    f = dict(_fields(buf))
    return f.get(1, 0), f.get(2, memoryview(b""))


def op_scopes(path: str) -> dict[str, dict[str, str]]:
    """Per chip, each device op's name -> the innermost named scope of its
    name path (its metadata's ``tf_op`` stat)."""
    out = {}
    space = memoryview(pathlib.Path(path).read_bytes())
    for field, plane in _fields(space):
        if field != 1:
            continue
        name, events, stat_names = "", [], {}
        for f, v in _fields(plane):
            if f == 2:
                name = bytes(v).decode()
            elif f == 4:
                events.append(v)
            elif f == 5:
                key, meta = _entry(v)
                stat_names[key] = bytes(dict(_fields(meta)).get(2, b"")
                                        ).decode()
        m = DEVICE_PLANE.match(name)
        if not m:
            continue
        scopes: dict[str, str] = {}
        for v in events:
            meta = _fields(_entry(v)[1])
            op, path_ = "", ""
            for f, x in meta:
                if f == 2:
                    op = bytes(x).decode()
                elif f == 5:
                    stat = dict(_fields(x))
                    if stat_names.get(stat.get(1, 0)) == PATH_STAT:
                        path_ = (bytes(stat[5]).decode() if 5 in stat
                                 else stat_names.get(stat.get(7, 0), ""))
            scopes[op] = scopes.get(op) or scope_of(path_)
        out[m.group(1)] = scopes
    return out


def extract(path: str) -> dict:
    """The events the reduction needs, as JSON-able lists; a device op is
    ``[name, start, end, category, scope]``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    chips, host, window = {}, [], None
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            ops, modules = [], []
            for line in plane.lines:
                if line.name == "XLA Ops":
                    for ev in line.events:
                        kind = _stat(ev, "hlo_category") or ""
                        ops.append([ev.name, int(ev.start_ns),
                                    int(ev.start_ns + ev.duration_ns),
                                    str(kind)])
                elif line.name == "XLA Modules":
                    modules.extend([ev.name, int(ev.start_ns),
                                    int(ev.start_ns + ev.duration_ns)]
                                   for ev in line.events)
            chips[m.group(1)] = {"ops": ops, "modules": modules}
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    item = [ev.name, int(ev.start_ns),
                            int(ev.start_ns + ev.duration_ns)]
                    if ev.name == WINDOW:
                        window = item[1:]
                    elif ev.duration_ns > 0:
                        host.append(item)
    scopes = op_scopes(path) if chips else {}
    for key, chip in chips.items():
        for op in chip["ops"]:
            op.append(scopes.get(key, {}).get(op[0], ""))
    return {"chips": chips, "host": host, "window": window}


def clip(extracted: dict) -> dict:
    """Only the events that overlap the window (to keep a trace small)."""
    lo, hi = extracted["window"]

    def keep(evs):
        return [e for e in evs if e[2] > lo and e[1] < hi]
    return {"window": [lo, hi], "host": keep(extracted["host"]),
            "chips": {k: {"ops": keep(c["ops"]), "modules": keep(c["modules"])}
                      for k, c in extracted["chips"].items()}}


# ------------------------------------------------------------------- reduce
def kind_of(name: str, category: str = "") -> str:
    """encode | decode | collective | compute."""
    for kind, pat in KERNELS.items():
        if pat.search(name):
            return kind
    if COLLECTIVE.search(name) or "collective" in category.lower():
        return "collective"
    return "compute"


def self_times(ops, lo: int, hi: int) -> dict[str, float]:
    """Nanoseconds of [lo, hi) attributed to each operation name, each
    instant to the innermost operation running then."""
    ev = sorted(((max(s, lo), min(e, hi), n) for n, s, e, *_ in ops
                 if e > lo and s < hi), key=lambda x: (x[0], -x[1]))
    out: dict[str, float] = {}
    stack: list[list] = []          # [start, end, name, cursor]

    def close_until(t):
        while stack and stack[-1][1] <= t:
            s, e, n, cur = stack.pop()
            out[n] = out.get(n, 0.0) + (e - cur)
            if stack:
                stack[-1][3] = max(stack[-1][3], e)

    for s, e, n in ev:
        close_until(s)
        if stack:
            top = stack[-1]
            if e > top[1]:          # overlaps its enclosing op's end: not
                e = top[1]          # nested; clip to what it covers
            out[top[2]] = out.get(top[2], 0.0) + max(0, s - top[3])
            top[3] = e
        if e > s:
            stack.append([s, e, n, s])
    close_until(float("inf"))
    return out


def union_ns(intervals, lo: int, hi: int) -> float:
    """Length of the union of [s, e) intervals clipped to [lo, hi)."""
    total, end = 0.0, lo
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s or e <= end:
            continue
        total += e - max(s, end)
        end = e
    return total


@dataclasses.dataclass
class Chip:
    """One chip's time inside the window, in seconds."""
    busy_s: float
    by_kind: dict[str, float]       # encode / decode / collective / compute
    by_op: dict[str, float]
    by_scope: dict[str, float]      # named scopes only (an op's fifth field)
    step_gaps_s: list[float]        # idle between consecutive step programs
    gaps: list[tuple[int, int]]     # idle intervals inside the window (ns)


@dataclasses.dataclass
class Reduced:
    """What the per-layer metric readers get."""
    window_s: float
    steps: int
    chips: list[Chip]
    host: list


def reduce(extracted: dict, steps: int) -> Reduced:
    """Per-chip busy time, self time by kind, by operation and by named
    scope, and the idle gaps, inside the benchmark's window annotation."""
    lo, hi = extracted["window"]
    chips = []
    for key in sorted(extracted["chips"], key=int):
        c = extracted["chips"][key]
        ops = c["ops"]
        if not ops:
            continue
        cats = {op[0]: (op[3] if len(op) > 3 else "") for op in ops}
        by_op = {n: t / 1e9 for n, t in self_times(ops, lo, hi).items()}
        by_kind = {"encode": 0.0, "decode": 0.0, "collective": 0.0,
                   "compute": 0.0}
        scopes = {op[0]: op[4] for op in ops if len(op) > 4 and op[4]}
        by_scope: dict[str, float] = {}
        for n, t in by_op.items():
            by_kind[kind_of(n, cats.get(n, ""))] += t
            if n in scopes:
                by_scope[scopes[n]] = by_scope.get(scopes[n], 0.0) + t
        spans = sorted((s, e) for _, s, e, *_ in ops if e > lo and s < hi)
        gaps, end = [], lo
        for s, e in spans:
            if s > end:
                gaps.append((end, s))
            end = max(end, e)
        if hi > end:
            gaps.append((end, hi))
        step_gaps = []
        mods = [m for m in c["modules"] if m[2] > lo and m[1] < hi]
        if mods:
            longest: dict[str, float] = {}
            for n, s, e in mods:
                longest[n] = longest.get(n, 0.0) + (e - s)
            step = max(longest, key=longest.get)
            runs = sorted((s, e) for n, s, e in mods if n == step)
            step_gaps = [(b[0] - a[1]) / 1e9 for a, b in zip(runs, runs[1:])]
        chips.append(Chip(busy_s=union_ns([(s, e) for _, s, e, *_ in ops],
                                          lo, hi) / 1e9,
                          by_kind=by_kind, by_op=by_op, by_scope=by_scope,
                          step_gaps_s=step_gaps, gaps=gaps))
    return Reduced(window_s=(hi - lo) / 1e9, steps=steps, chips=chips,
                   host=extracted["host"])


# ---------------------------------------------------------------- breakdown
def breakdown(r: Reduced, top: int = 10) -> dict:
    """The operations that took most device time (mean over chips) and the
    longest idle gaps of the busiest chip, each named by the innermost host
    event that covers most of it."""
    if not r.chips:
        return {"device_ops": [], "idle_gaps": []}
    ops: dict[str, float] = {}
    for c in r.chips:
        for n, t in c.by_op.items():
            ops[n] = ops.get(n, 0.0) + t / len(r.chips)
    busiest = max(r.chips, key=lambda c: c.busy_s)
    gaps = sorted(busiest.gaps, key=lambda g: g[0] - g[1])[:top]
    named = []
    for s, e in gaps:
        best, best_key = "no host event", None
        for n, hs, he in r.host:
            cover = min(e, he) - max(s, hs)
            if cover <= 0:
                continue
            key = (cover, -(he - hs))
            if best_key is None or key > best_key:
                best, best_key = n, key
        named.append([best, (e - s) / 1e9])
    return {"device_ops": sorted(([n, t] for n, t in ops.items()),
                                 key=lambda x: -x[1])[:top],
            "idle_gaps": named}

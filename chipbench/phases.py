"""Host spans and named phases in a profiler trace: where the time between
two steps goes, and which phase of the coded step each device op belongs to.

The program marks its host work with spans (``repro.train.trainer``:
``trainer.inputs``, ``trainer.dispatch``, ``trainer.sync``,
``trainer.readback``, ``trainer.telemetry``, ``trainer.checkpoint``) and
its device work with named scopes (``repro.train.coded_step``:
``coded.grad``, ``coded.encode``, ``coded.exchange``, ``coded.decode``,
``coded.apply``).  A scope reaches a TPU trace inside each op's name path
(the ``tf_op`` stat of an ``XLA Ops`` event); an op belongs to the
innermost ``<layer>.<phase>`` part of that path, and to ``""`` where it has
none: the fifth field ``reduce_trace.extract`` gives each device op.

Built on ``reduce_trace``'s lists and rules:

``reduce(extracted)``  per chip, self time inside the window by
                   scope (``by_scope``), and for each pair of consecutive
                   step programs (the pairs ``step_gaps_s`` uses) the
                   nanoseconds of the gap that each span's host events
                   cover (``gap_spans``).
``metrics(...)``   the numbers below, by name.
``idle_gaps(...)`` the busiest chip's longest idle gaps, each named by the
                   span that covers most of it.

As a script, on a trace kept by ``run.py --keep-trace DIR``::

  python3 chipbench/phases.py DIR/<cell>.xplane.pb --steps 5

prints one JSON object: the metrics, the time by scope, the largest ops
with their scope and the named idle gaps.
"""
from __future__ import annotations

import argparse
import dataclasses
import gzip
import json
import sys

import reduce_trace as rt

SPANS = ("trainer.inputs", "trainer.dispatch", "trainer.sync",
         "trainer.readback", "trainer.telemetry", "trainer.checkpoint")
SCOPES = ("coded.grad", "coded.encode", "coded.exchange", "coded.decode",
          "coded.apply")
# per-layer numbers: a device phase's self time per step, or a host span's
# cover of the gap between two step programs
BY_SCOPE = {"fwd_bwd_ms": "coded.grad", "apply_ms": "coded.apply"}
BY_SPAN = {"gap_sync_ms": "trainer.sync",
           "gap_readback_ms": "trainer.readback",
           "gap_inputs_ms": "trainer.inputs",
           "gap_dispatch_ms": "trainer.dispatch"}


# ------------------------------------------------------------------- reduce
def step_runs(modules, lo: int, hi: int) -> list[tuple[int, int]]:
    """The step program's runs inside the window, in order: the program
    with the most time there (``reduce_trace.reduce``'s rule)."""
    mods = [m for m in modules if m[2] > lo and m[1] < hi]
    if not mods:
        return []
    total: dict[str, float] = {}
    for n, s, e in mods:
        total[n] = total.get(n, 0.0) + (e - s)
    step = max(total, key=total.get)
    return sorted((s, e) for n, s, e in mods if n == step)


def span_events(host, name: str) -> list[tuple[int, int]]:
    return [(s, e) for n, s, e in host if n == name]


@dataclasses.dataclass
class ChipPhases:
    """One chip's phases inside the window."""
    by_scope: dict[str, float]         # seconds of self time by scope
    by_op: dict[tuple[str, str], float]  # (op, scope) -> seconds
    gaps: list[tuple[int, int]]        # between consecutive step programs
    gap_spans: dict[str, list[float]]  # span -> ns covered, one per gap;
                                       # spans the trace holds only


def reduce(extracted: dict) -> list[ChipPhases]:
    """Self time by scope, and each span's cover of each inter-step gap,
    per chip with ops (the chips ``reduce_trace.reduce`` keeps)."""
    lo, hi = extracted["window"]
    host = extracted["host"]
    out = []
    for key in sorted(extracted["chips"], key=int):
        c = extracted["chips"][key]
        if not c["ops"]:
            continue
        keyed = [[(op[0], op[4] if len(op) > 4 else ""), op[1], op[2]]
                 for op in c["ops"]]
        by_op = {k: t / 1e9 for k, t in rt.self_times(keyed, lo, hi).items()}
        by_scope: dict[str, float] = {}
        for (_, scope), t in by_op.items():
            by_scope[scope] = by_scope.get(scope, 0.0) + t
        runs = step_runs(c["modules"], lo, hi)
        gaps = [(a[1], b[0]) for a, b in zip(runs, runs[1:])]
        gap_spans = {name: [rt.union_ns(events, s, e) for s, e in gaps]
                     for name in SPANS
                     if (events := span_events(host, name))}
        out.append(ChipPhases(by_scope, by_op, gaps, gap_spans))
    return out


def metrics(chips: list[ChipPhases], steps: int) -> dict[str, float]:
    """Per step, mean over chips: each phase's self time (ms); per gap,
    mean over chips and gaps: each span's cover (ms).  A number whose
    phase or span the trace does not hold is left out."""
    out: dict[str, float] = {}
    if not chips or steps <= 0:
        return out
    for name, scope in BY_SCOPE.items():
        if any(scope in c.by_scope for c in chips):
            t = [c.by_scope.get(scope, 0.0) for c in chips]
            out[name] = 1e3 * sum(t) / len(t) / steps
    for name, span in BY_SPAN.items():
        cover = [ns for c in chips for ns in c.gap_spans.get(span, [])]
        if cover:
            out[name] = sum(cover) / len(cover) / 1e6
    return out


def idle_gaps(reduced: rt.Reduced, top: int = 10) -> list:
    """The busiest chip's longest idle gaps, each named by the span of
    ``SPANS`` whose host events cover most of it; where none covers any
    of it, by ``reduce_trace.breakdown``'s rule."""
    if not reduced.chips:
        return []
    # breakdown lists the same gaps of the same chip in the same order
    fallback = rt.breakdown(reduced, top)["idle_gaps"]
    busiest = max(reduced.chips, key=lambda c: c.busy_s)
    gaps = sorted(busiest.gaps, key=lambda g: g[0] - g[1])[:top]
    named = []
    for (s, e), old in zip(gaps, fallback):
        cover = {n: rt.union_ns(span_events(reduced.host, n), s, e)
                 for n in SPANS}
        best = max(cover, key=cover.get)
        named.append([best if cover[best] > 0 else old[0], (e - s) / 1e9])
    return named


def span_ms(extracted: dict) -> dict[str, float]:
    """Each span's mean length inside the window, on the host's clock
    alone (ms)."""
    lo, hi = extracted["window"]
    out = {}
    for name in SPANS:
        d = [e - s for s, e in span_events(extracted["host"], name)
             if lo <= s < hi]
        if d:
            out[name] = sum(d) / len(d) / 1e6
    return out


def clock_disagreements(extracted: dict) -> list[str]:
    """Where the host's spans and the device's step programs disagree on
    order: the k-th step program inside the window must start after the
    k-th ``trainer.dispatch`` span starts and end before the k-th
    ``trainer.sync`` span ends."""
    lo, hi = extracted["window"]
    host = extracted["host"]
    dispatch = sorted(s for s in span_events(host, "trainer.dispatch")
                      if lo <= s[0] < hi)
    sync = sorted(s for s in span_events(host, "trainer.sync")
                  if lo <= s[0] < hi)
    bad = []
    for key, c in sorted(extracted["chips"].items()):
        runs = step_runs(c["modules"], lo, hi)
        if not (len(runs) == len(dispatch) == len(sync)):
            bad.append(f"chip {key}: {len(runs)} step programs, "
                       f"{len(dispatch)} dispatch and {len(sync)} sync spans")
            continue
        for k, ((rs, re_), (ds, _), (_, se)) in enumerate(
                zip(runs, dispatch, sync)):
            if rs < ds:
                bad.append(f"chip {key} step {k}: program starts {ds - rs} "
                           f"ns before its dispatch")
            if se < re_:
                bad.append(f"chip {key} step {k}: program ends {re_ - se} "
                           f"ns after its sync")
    return bad


# ------------------------------------------------------------------- script
def summary(extracted: dict, steps: int, top: int = 15) -> dict:
    """What the script prints: the metrics, the step gap and compute as
    ``reduce_trace`` reads them, time by scope, the largest ops with their
    scope, the named idle gaps and any clock disagreement."""
    chips = reduce(extracted)
    reduced = rt.reduce(extracted, steps)
    n = max(1, len(chips))
    per_step = {s: 1e3 * sum(c.by_scope.get(s, 0.0) for c in chips)
                / n / steps for s in SCOPES + ("",)}
    ops: dict = {}
    for c in chips:
        for k, t in c.by_op.items():
            ops[k] = ops.get(k, 0.0) + 1e3 * t / n / steps
    gaps = [g for c in reduced.chips for g in c.step_gaps_s]
    compute = [c.by_kind["compute"] for c in reduced.chips]
    return {
        "metrics": metrics(chips, steps),
        "step_gap_ms": 1e3 * sum(gaps) / len(gaps) if gaps else None,
        "compute_ms": 1e3 * sum(compute) / n / steps if compute else None,
        "scope_ms_per_step": per_step,
        "span_ms_per_step": span_ms(extracted),
        "top_ops_ms_per_step": [[name, scope, t] for (name, scope), t in
                                sorted(ops.items(), key=lambda x: -x[1])
                                [:top]],
        "idle_gaps": idle_gaps(reduced),
        "clock_disagreements": clock_disagreements(extracted),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("trace", help="an .xplane.pb, or an events file "
                    "(.json.gz) this script wrote with --record")
    ap.add_argument("--steps", type=int, required=True,
                    help="the steps the trace's window holds")
    ap.add_argument("--record", default=None,
                    help="write the window's device events, each op with "
                    "its scope, its trainer spans and step annotations, "
                    "and the summary here (.json.gz)")
    args = ap.parse_args(argv)
    if args.trace.endswith(".xplane.pb"):
        extracted = rt.extract(args.trace)
    else:
        with gzip.open(args.trace, "rt") as f:
            extracted = json.load(f)
    result = summary(extracted, args.steps)
    if args.record:
        kept = rt.clip(extracted)
        kept["host"] = [h for h in kept["host"]
                        if h[0] in SPANS or h[0] == "train"]
        with gzip.open(args.record, "wt") as f:
            json.dump(dict(kept, steps=args.steps,
                           summary=summary(kept, args.steps)), f)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())

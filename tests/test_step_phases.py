"""The trainer's host spans and the coded step's named phases.

- ``Trainer.step`` leaves its ``trainer.*`` spans in a profiler trace, one
  set per step, in step order and without overlap, on the synchronous and
  the pipelined path; ``trainer.telemetry`` and ``trainer.checkpoint`` only
  where there is telemetry to record or a checkpoint to write.  The host
  events are read the way the chip benchmark reads them
  (``chipbench/reduce_trace.py``).
- Every ``coded.*`` scope of a step body reaches its compiled HLO's op
  metadata, which is where a device trace reads it from.
"""
import dataclasses
import importlib.util
import pathlib
import re
import sys

import jax
import numpy as np
import pytest

import repro.coding as coding
from repro.configs import get_config
from repro.core import make_code
from repro.core.runtime_model import RuntimeParams
from repro.data import CodedBatcher, make_synthetic_batch
from repro.launch.mesh import make_local_mesh
from repro.models import api as model_api
from repro.optim import get_optimizer
from repro.train import Trainer
from repro.train.coded_step import make_coded_train_step
from repro.tune import ShiftedExpSampler

ROOT = pathlib.Path(__file__).resolve().parents[1]
CODE = make_code(4, 3, 1, 2)
STEP_SPANS = ["trainer.inputs", "trainer.dispatch", "trainer.sync",
              "trainer.readback"]
ALL_SCOPES = {"coded.grad", "coded.encode", "coded.exchange", "coded.decode",
              "coded.apply"}


def _reduce_trace():
    name = "chipbench_reduce_trace"
    if name not in sys.modules:
        spec = importlib.util.spec_from_file_location(
            name, ROOT / "chipbench" / "reduce_trace.py")
        sys.modules[name] = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(sys.modules[name])
    return sys.modules[name]


def _cfg():
    return dataclasses.replace(get_config("logistic-paper"), d_model=64)


def _traced_spans(tr, batches, warm, tmp_path):
    """Step ``tr`` through ``batches``, tracing all but the first ``warm``;
    the ``trainer.*`` host spans of the trace as (start, end, name)."""
    for b in batches[:warm]:
        tr.step(b)
    with jax.profiler.trace(str(tmp_path)):
        for b in batches[warm:]:
            tr.step(b)
    (path,) = tmp_path.glob("plugins/profile/*/*.xplane.pb")
    host = _reduce_trace().extract(str(path))["host"]
    return sorted((s, e, n) for n, s, e in host if n.startswith("trainer."))


def _assert_in_order(spans, names):
    assert [n for _, _, n in spans] == names
    for (_, end, a), (start, _, b) in zip(spans, spans[1:]):
        assert end <= start, (a, b)


@pytest.mark.parametrize("pipelined", [False, True],
                         ids=["sync", "pipelined"])
def test_trainer_step_spans_in_order(tmp_path, pipelined):
    """Two traced steps each show inputs, dispatch, sync and readback, in
    that order and apart (the pipelined path warms up through its fill and
    first steady call, which compile)."""
    cfg = _cfg()
    tr = Trainer(cfg, CODE, make_local_mesh(4, 1), get_optimizer("sgd", 1e-2),
                 spec=coding.SchemeSpec(pipelined=pipelined))
    rng = np.random.default_rng(0)
    batches = [make_synthetic_batch(rng, cfg, 16, 0) for _ in range(4)]
    spans = _traced_spans(tr, batches, 2, tmp_path)
    _assert_in_order(spans, STEP_SPANS * 2)


def test_telemetry_and_checkpoint_spans_only_when_used(tmp_path):
    """With a timed straggler source every step records telemetry; a
    checkpoint span appears on the step that saves (every second one)."""
    cfg = _cfg()
    sampler = ShiftedExpSampler(
        RuntimeParams(n=4, lambda1=0.5, lambda2=0.2, t1=0.5, t2=16.0), seed=1)
    tr = Trainer(cfg, CODE, make_local_mesh(4, 1), get_optimizer("sgd", 1e-2),
                 straggler_source=sampler,
                 checkpoint_dir=str(tmp_path / "ckpt"), checkpoint_every=2)
    rng = np.random.default_rng(0)
    batches = [make_synthetic_batch(rng, cfg, 16, 0) for _ in range(3)]
    spans = _traced_spans(tr, batches, 1, tmp_path / "trace")
    step = STEP_SPANS + ["trainer.telemetry"]
    _assert_in_order(spans, step + ["trainer.checkpoint"] + step)


def _compiled_hlo(kind: str) -> str:
    """Compiled HLO text of one step program at toy size on the CPU."""
    cfg = _cfg()
    opt = get_optimizer("adamw", 1e-3)
    spec = {"gather": coding.SchemeSpec(),
            "a2a": coding.SchemeSpec(schedule="a2a"),
            "psum": coding.SchemeSpec(schedule="psum"),
            "pipelined-steady": coding.SchemeSpec(pipelined=True)}[kind]
    arts = make_coded_train_step(cfg, CODE, make_local_mesh(4, 1), opt,
                                 spec=spec)
    batch = jax.tree.map(jax.numpy.asarray, CodedBatcher(CODE).place(
        make_synthetic_batch(np.random.default_rng(0), cfg, 16, 0)))
    params = model_api.init(jax.random.PRNGKey(0), cfg)
    state = opt.init(params)
    inp = arts.step_inputs(())
    if kind != "pipelined-steady":
        return arts.compiled(batch).lower(
            params, state, batch, inp["W"], inp["mask"],
            inp["rho"]).compile().as_text()
    cp = arts.compiled_pipeline(batch, donate=False)
    wire = cp.fill(params, batch, inp["mask"], inp["rho"])
    return cp.steady.lower(params, state, batch, inp["W"], inp["mask"],
                           inp["rho"], *wire).compile().as_text()


@pytest.mark.parametrize("kind,scopes", [
    ("gather", ALL_SCOPES),
    ("a2a", ALL_SCOPES),
    ("pipelined-steady", ALL_SCOPES),
    ("psum", {"coded.grad", "coded.exchange", "coded.apply"}),
])
def test_compiled_step_holds_its_phase_scopes(kind, scopes):
    """Each phase its body has, and no other ``coded.*`` name, appears in
    the op metadata of the compiled step."""
    hlo = _compiled_hlo(kind)
    found = {s for path in re.findall(r'op_name="([^"]*)"', hlo)
             for s in re.findall(r"coded\.[A-Za-z_]+", path)}
    assert found == scopes

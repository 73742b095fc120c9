"""SchemeSpec: the one value object for every scheme lever.

Pins the API contracts: the spec holds exactly the five levers; the
per-lever kwargs it replaced are gone from ``make_coded_train_step`` and
the ``Trainer`` (``TypeError``); the spec's validation reproduces the
historical error messages; and the Trainer's legacy straggler fields map
onto the StragglerSource protocol with deprecation warnings.
"""
import dataclasses
import warnings

import numpy as np
import pytest

import repro.coding as coding
from repro.configs import get_config
from repro.core import make_code
from repro.data import make_synthetic_batch
from repro.launch.mesh import make_local_mesh
from repro.optim import get_optimizer
from repro.train.coded_step import make_coded_train_step
from repro.train.trainer import Trainer
from repro.tune import (FixedStragglers, NoStragglers, RandomStragglers,
                        StragglerSource, TimedSource, as_straggler_source)

CODE = make_code(4, 3, 1, 2)

# the per-lever kwargs SchemeSpec replaced; neither builder accepts them
REMOVED_KWARGS = {"schedule": "a2a", "backend": "ref", "packed": False,
                  "partial": True, "pipelined": True, "fuse_apply": True,
                  "encode_dtype": "bfloat16"}


def _linear_cfg():
    return dataclasses.replace(get_config("logistic-paper"), d_model=64)


# ----------------------------------------------------------- the value object
def test_spec_is_frozen_and_replace_works():
    assert [f.name for f in dataclasses.fields(coding.SchemeSpec)] == [
        "schedule", "backend", "partial", "pipelined", "encode_dtype"]
    spec = coding.SchemeSpec(schedule="a2a", partial=False)
    with pytest.raises(dataclasses.FrozenInstanceError):
        spec.schedule = "gather"
    spec2 = spec.replace(partial=True)
    assert spec2.schedule == "a2a" and spec2.partial and not spec.partial


def test_spec_validation_reproduces_historical_messages():
    with pytest.raises(ValueError, match="partial"):
        coding.SchemeSpec(pipelined=True, partial=True)
    with pytest.raises(ValueError, match="encoding"):
        coding.SchemeSpec(pipelined=True, schedule="psum")
    with pytest.raises(ValueError, match="encoding"):
        coding.SchemeSpec(pipelined=True,
                          schedule=coding.get_schedule("psum"))


def test_spec_and_kwargs_cannot_mix():
    """The per-lever kwargs are gone: each raises ``TypeError``, with or
    without ``spec=``."""
    cfg = _linear_cfg()
    mesh = make_local_mesh(4, 1)
    opt = get_optimizer("sgd", 1e-2)
    for name, value in REMOVED_KWARGS.items():
        with pytest.raises(TypeError, match=name):
            make_coded_train_step(cfg, CODE, mesh, opt, **{name: value})
        with pytest.raises(TypeError, match=name):
            make_coded_train_step(cfg, CODE, mesh, opt,
                                  spec=coding.SchemeSpec(), **{name: value})


def test_spec_threads_through_step_artifacts():
    cfg = _linear_cfg()
    mesh = make_local_mesh(4, 1)
    opt = get_optimizer("sgd", 1e-2)
    spec = coding.SchemeSpec(schedule="gather", backend="ref")
    arts = make_coded_train_step(cfg, CODE, mesh, opt, spec=spec)
    assert arts.spec is spec
    assert arts.codec.backend.name == "ref"


# -------------------------------------------------------- straggler sources
def test_as_straggler_source_dispatch():
    assert isinstance(as_straggler_source(None), NoStragglers)
    src = FixedStragglers((1, 2))
    assert as_straggler_source(src) is src
    assert isinstance(src, StragglerSource)
    timed = as_straggler_source(lambda step, code: None)
    assert isinstance(timed, TimedSource) and timed.provides_times
    with pytest.raises(TypeError):
        as_straggler_source(42)


def test_fixed_and_random_sources_draw_within_design():
    fixed = FixedStragglers((2,))
    d = fixed.draw(0, CODE)
    assert d.stragglers == (2,) and d.times is None
    rnd = RandomStragglers(seed=1)
    seen = set()
    for t in range(32):
        st = rnd.draw(t, CODE).stragglers
        assert len(st) <= CODE.s
        seen.add(st)
    assert len(seen) > 1               # actually random
    # deterministic across instances with one seed
    a = [RandomStragglers(seed=9).draw(t, CODE).stragglers
         for t in range(8)]
    b = [RandomStragglers(seed=9).draw(t, CODE).stragglers
         for t in range(8)]
    assert a == b


def test_trainer_legacy_straggler_fields_warn_and_map():
    cfg = _linear_cfg()
    mesh = make_local_mesh(4, 1)
    opt = get_optimizer("sgd", 1e-2)
    with pytest.warns(DeprecationWarning, match="straggler_source"):
        tr = Trainer(cfg=cfg, code=CODE, mesh=mesh, optimizer=opt,
                     straggler_mode="fixed", fixed_stragglers=(1,))
    assert isinstance(tr._source, FixedStragglers)
    assert tr._source.draw(0, CODE).stragglers == (1,)
    with pytest.warns(DeprecationWarning, match="straggler_source"):
        tr = Trainer(cfg=cfg, code=CODE, mesh=mesh, optimizer=opt,
                     straggler_mode="random", seed=3)
    assert isinstance(tr._source, RandomStragglers)
    tr = Trainer(cfg=cfg, code=CODE, mesh=mesh, optimizer=opt)
    assert isinstance(tr._source, NoStragglers)


def test_trainer_rejects_source_plus_legacy_fields():
    cfg = _linear_cfg()
    mesh = make_local_mesh(4, 1)
    opt = get_optimizer("sgd", 1e-2)
    with pytest.raises(ValueError, match="straggler_source"):
        Trainer(cfg=cfg, code=CODE, mesh=mesh, optimizer=opt,
                straggler_source=NoStragglers(), straggler_mode="random")
    with pytest.raises(ValueError, match="straggler"):
        Trainer(cfg=cfg, code=CODE, mesh=mesh, optimizer=opt,
                straggler_mode="nope")


def test_trainer_spec_kwarg_and_legacy_kwargs():
    """The Trainer reads its scheme from ``spec`` alone: the per-lever
    kwargs raise ``TypeError``, and no mirror fields remain."""
    cfg = _linear_cfg()
    mesh = make_local_mesh(4, 1)
    opt = get_optimizer("sgd", 1e-2)
    spec = coding.SchemeSpec(schedule="a2a", backend="ref")
    tr = Trainer(cfg=cfg, code=CODE, mesh=mesh, optimizer=opt, spec=spec)
    assert tr.spec == spec and tr.spec.schedule == "a2a"
    assert tr.arts.spec.schedule == "a2a"
    fields = {f.name for f in dataclasses.fields(Trainer)}
    assert not fields & set(REMOVED_KWARGS)
    for name, value in REMOVED_KWARGS.items():
        with pytest.raises(TypeError, match=name):
            Trainer(cfg=cfg, code=CODE, mesh=mesh, optimizer=opt,
                    **{name: value})


def test_trainer_runs_one_step_from_spec():
    """The spec-built Trainer trains: one real step on the host mesh with
    a warning-free construction."""
    cfg = _linear_cfg()
    mesh = make_local_mesh(4, 1)
    opt = get_optimizer("sgd", 1e-2)
    with warnings.catch_warnings():
        warnings.simplefilter("error", DeprecationWarning)
        tr = Trainer(cfg=cfg, code=CODE, mesh=mesh, optimizer=opt,
                     spec=coding.SchemeSpec(schedule="gather"),
                     straggler_source=FixedStragglers((2,)))
    rng = np.random.default_rng(5)
    batch = make_synthetic_batch(rng, cfg, 16, 0)
    metrics = tr.step(batch)
    assert np.isfinite(float(np.asarray(metrics["loss"]).reshape(-1)[0]))

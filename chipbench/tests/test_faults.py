"""A whole run with the timed path broken underneath comes out not correct,
once for each fault a training cell can have; the sound run comes out
correct.  The look for a chip is skipped, the sizes are cut to the CPU
(``tiny.py``), and the cell's own limits decide."""
import jax.numpy as jnp
import pytest

import tiny


def state_unchanged(mp):
    """The optimizer returns the parameters and its state as they were."""
    from repro.optim import optimizers

    real = optimizers.adamw

    def adamw(*a, **kw):
        opt = real(*a, **kw)
        return optimizers.Optimizer(opt.init, lambda g, s, p: (p, s))
    mp.setattr(optimizers, "adamw", adamw)


def half_batch(mp):
    """Each subset's loss, and so its gradient, is the mean over the first
    half of its rows only."""
    from repro.models import dense

    real = dense.loss

    def loss(params, cfg, batch):
        half = batch["tokens"].shape[0] // 2
        return real(params, cfg, {k: v[:half] for k, v in batch.items()})
    mp.setattr(dense, "loss", loss)


def no_exchange(mp):
    """The all-gather between chips is left out: every worker decodes its
    own encoding in the place of all n."""
    from repro.coding import wire

    def gather(x, axis_names):
        return jnp.broadcast_to(x[None], (4,) + x.shape)
    mp.setattr(wire, "all_gather_wire", gather)


def answer_altered(mp):
    """The loss, the step's answer, is altered where it is produced (one
    part in a hundred), and with it the gradient."""
    from repro.models import common

    real = common.softmax_xent
    mp.setattr(common, "softmax_xent",
               lambda *a, **kw: real(*a, **kw) * 1.01)


FAULTS = {"state_unchanged": state_unchanged, "half_batch": half_batch,
          "no_exchange": no_exchange, "answer_altered": answer_altered}


@pytest.mark.parametrize("workload", tiny.CELLS)
def test_sound_run_is_correct(workload):
    result = tiny.run(tiny.cell(workload))
    assert result["correct"], result["compared"]


# one worker has no exchange between chips to leave out
CASES = [(w, f) for w in tiny.CELLS for f in sorted(FAULTS)
         if not (f == "no_exchange" and w == "qwen3-8b.worker")]


@pytest.mark.parametrize("workload,fault", CASES)
def test_fault_is_not_correct(workload, fault, monkeypatch):
    FAULTS[fault](monkeypatch)
    result = tiny.run(tiny.cell(workload))
    assert not result["correct"], result["compared"]

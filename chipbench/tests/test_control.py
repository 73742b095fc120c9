"""The control, the reference with its bfloat16 activations in float8,
put in the program's place, comes out not correct.  At the CPU's size and
its limits (``tiny.py``); on the chip at the cell's size,
``calibrate.py --control`` reads the same numbers."""
import pytest

import tiny
import bench
import check
import reference


def readings(cell, seed, **kw):
    wseed, dseed, _ = bench.derive_seeds(seed)
    k = cell.model.dims(cell.config)
    feed = bench.Feed(k.vocab, cell.traffic, dseed)
    batches = [feed.next() for _ in range(bench.FIRST_STEPS)]
    return reference.train_readings(cell.model, wseed, k, batches,
                                    cell.traffic["optimizer"], **kw)


@pytest.mark.parametrize("seed", [11, 2**31 + 12, 13])
@pytest.mark.parametrize("workload", tiny.CELLS)
def test_control_is_not_correct(workload, seed):
    cell = tiny.cell(workload)
    numbers = check.gaps(readings(cell, seed, quant="fp8"),
                         readings(cell, seed))
    correct, shown = check.verdict(numbers, cell.limits)
    assert not correct, shown

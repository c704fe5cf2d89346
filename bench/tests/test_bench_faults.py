"""A whole train run, chip look skipped, with the timed path broken
underneath: every fault a one-chip train cell can have turns ``correct``
false by the cells' own limits."""
import time

import pytest

import faults
import spec

train = spec.driver("train")
LIMITS = "mamba2-1.3b.train-2k"


@pytest.mark.parametrize("fault", sorted(faults.FAULTS))
def test_fault_is_not_correct(fault, tiny, tmp_path):
    cfg, traffic = tiny
    res = train.run(cfg, traffic, spec.limits(LIMITS),
                    seed=2 ** 31 + 4343, seconds=0.2, trace=False,
                    t0=time.perf_counter(), out_dir=tmp_path,
                    step_wrapper=faults.FAULTS[fault])
    assert not res["correct"], res["checks"]

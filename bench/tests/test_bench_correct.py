"""A whole train run, chip look skipped, at a size a test run holds, and the
control at that size.

The program as configured comes out correct by the cell's own limits, and
on the CPU, where both run their products in float32, it agrees with the
plain reference to float32 rounding: at most 1.5e-7 (loss), 9.0e-7
(gradient) and 1.3e-5 (change) over eight seeds at this size.

The control is the plain reference with its products in int8
(``reference.int8_mm``), one precision step below the program's single
bfloat16 pass on the chip; put in the program's place it fails the cell's
own limits.
"""
import time

import reference
import spec
from traffic import TokenFeed

train = spec.driver("train")
LIMITS = "mamba2-1.3b.train-2k"
TEST_SIZE_LIMITS = {"loss_gap": 5e-6, "grad_gap": 2e-4, "change_gap": 2e-4}
SEED = 2 ** 31 + 4242


def test_program_is_correct(tiny, tmp_path):
    cfg, traffic = tiny
    res = train.run(cfg, traffic, spec.limits(LIMITS), seed=SEED,
                    seconds=0.2, trace=False, t0=time.perf_counter(),
                    out_dir=tmp_path)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > traffic["check_steps"]
    assert res["e2e"]["train_tokens_per_s"] > 0 and res["e2e"]["setup_s"] > 0
    assert all(c["value"] <= TEST_SIZE_LIMITS[k]
               for k, c in res["checks"].items()), res["checks"]


def test_control_is_not_correct(tiny):
    cfg, traffic = tiny
    tokens = TokenFeed.from_traffic(traffic, cfg["vocab_size"], SEED)
    ref = train.reference_readings(cfg, traffic, SEED, tokens.batch)
    control = train.reference_readings(cfg, traffic, SEED, tokens.batch,
                                       mm=reference.int8_mm)
    got = train.gaps(control, ref)
    limits = spec.limits(LIMITS)
    assert any(got[k] > limits[k] for k in limits), got

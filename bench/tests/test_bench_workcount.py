"""Work counts against values worked out by hand."""
import pytest

import workcount

PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def _cfg(d_state, n_groups=1):
    return {"d_model": 2048, "n_layers": 16, "padded_vocab": 50432,
            "ssm": {"d_state": d_state, "d_conv": 4, "expand": 2,
                    "head_dim": 64, "chunk": 256, "n_groups": n_groups}}


def test_ssd_kernel_work_mamba2_shape():
    # B=2, L=2048, H=64, P=64, N=128, G=1, Q=256 -> 16 (batch, chunk) cells
    w = workcount.ssd_kernel_work(_cfg(128), batch=2, seq=2048)
    fwd_flops = 16 * (2 * 256 * 256 * 128 + 64 * (2 * 256 * 256 * 64
                                                  + 2 * 256 * 128 * 64))
    assert w["fwd"][0] == fwd_flops == 13_153_337_344
    x = 2 * 2048 * 64 * 64 * 4            # 67,108,864
    vec = 2 * 2048 * 64 * 4               # dt or cs
    bc = 2 * 2048 * 1 * 128 * 4           # B or C at G = 1
    states = 16 * 64 * 128 * 64 * 4
    assert w["fwd"][1] == 2 * x + 2 * vec + 2 * bc + states == 174_063_616
    assert w["bwd"][1] == w["fwd"][1] + x + 2 * vec + 2 * bc
    t, bound = workcount.least_time(*w["fwd"], PEAK)
    assert bound == "bytes" and t == pytest.approx(174_063_616 / 819e9)


def test_ssd_bytes_count_b_and_c_at_groups_not_heads():
    one = workcount.ssd_kernel_work(_cfg(64, n_groups=1), batch=1, seq=4096)
    eight = workcount.ssd_kernel_work(_cfg(64, n_groups=8), batch=1,
                                      seq=4096)
    bc = 1 * 4096 * 64 * 4                # one group's B or C, fp32
    # forward reads B and C: 2 x 7 more groups; backward adds dB and dC
    assert eight["fwd"][1] - one["fwd"][1] == 2 * 7 * bc
    assert eight["bwd"][1] - one["bwd"][1] == 4 * 7 * bc
    # a per-head copy (H = 64) would have counted 2 x 63 x bc more
    assert one["fwd"][1] < 2 * 64 * bc + 2 * 1 * 4096 * 64 * 64 * 4


def test_train_flops_per_token_counts_params_and_mixing():
    cfg = _cfg(128)
    n = workcount.param_count(cfg)
    per_layer = (2 * 2048 * 4096 + 2 * 2048 * 128 + 2048 * 64
                 + (4096 + 256) * 5 + 3 * 64 + 4096 + 4096 * 2048 + 2048)
    assert n == 50432 * 2048 + 2048 + 16 * per_layer == 516_875_264
    ssd = 2 * 256 * 128 + 64 * (2 * 256 * 64 + 4 * 128 * 64)
    assert workcount.train_flops_per_token(cfg) == \
        6.0 * n + 3.0 * 16 * ssd

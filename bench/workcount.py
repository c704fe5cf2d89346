"""Operations and bytes from shapes: the work a step or a kernel call needs.

These counts are the algorithm's, not any implementation's, so they stay the
same whatever computes them: a faster kernel reads as a higher share of its
roofline, never as less work.
"""
from __future__ import annotations

F32 = 4


def ssm_dims(cfg: dict) -> dict:
    s = cfg["ssm"]
    d = cfg["d_model"]
    di = s["expand"] * d
    return {"d": d, "di": di, "H": di // s["head_dim"], "P": s["head_dim"],
            "N": s["d_state"], "G": s["n_groups"], "Q": s["chunk"],
            "K": s["d_conv"]}


def param_count(cfg: dict) -> int:
    """Parameters held, as the configuration builds them."""
    d, V = cfg["d_model"], cfg["padded_vocab"]
    m = ssm_dims(cfg)
    di, H, G, N, K = m["di"], m["H"], m["G"], m["N"], m["K"]
    gn = G * N
    mixer = (2 * d * di + 2 * d * gn + d * H          # in projections
             + (di + 2 * gn) * (K + 1)                # depthwise conv + bias
             + 3 * H + di + di * d)                   # dt_bias, A_log, D, norm, out
    return V * d + d + cfg["n_layers"] * (mixer + d)


def ssd_fwd_flops_per_token(cfg: dict) -> float:
    """The SSD scan's forward operations per token and layer (chunked form):
    scores C.B at the group count, the decayed product with x, the chunk
    states and the state read-out per head."""
    m = ssm_dims(cfg)
    Q, N, P, H, G = m["Q"], m["N"], m["P"], m["H"], m["G"]
    return 2 * G * Q * N + H * (2 * Q * P + 2 * N * P + 2 * N * P)


def train_flops_per_token(cfg: dict) -> float:
    """Operations the forward and backward passes require per token:
    6 x the parameters (the tied embedding takes part as the output head),
    plus 3 x the SSD scan's products, which no parameter counts.
    Recomputation is not counted."""
    return 6.0 * param_count(cfg) + 3.0 * cfg["n_layers"] \
        * ssd_fwd_flops_per_token(cfg)


def ssd_kernel_work(cfg: dict, batch: int, seq: int) -> dict:
    """Least operations and bytes of one intra-chunk SSD kernel call over a
    layer, forward and backward.

    Forward, per chunk: scores C.B (2 Q^2 N per group), the decayed product
    with x (2 Q^2 P per head) and the chunk end state (2 Q N P per head).
    Backward: the scores again, dC and dB (2 Q^2 N per group each), dM and dx
    (2 Q^2 P per head each), and the state path (2 x 2 Q N P per head).
    Bytes: x, dt, cs in and y, states out (backward: their cotangents), all
    fp32, with B and C (and dB, dC) counted at the group count G, not as the
    per-head copies an implementation may make.
    """
    m = ssm_dims(cfg)
    Q, N, P, H, G = m["Q"], m["N"], m["P"], m["H"], m["G"]
    nc = -(-seq // Q)
    cells = batch * nc
    fwd_flops = cells * (2 * G * Q * Q * N + H * (2 * Q * Q * P + 2 * Q * N * P))
    bwd_flops = cells * (3 * 2 * G * Q * Q * N
                         + H * (2 * 2 * Q * Q * P + 2 * 2 * Q * N * P))
    L = nc * Q
    x = batch * L * H * P * F32
    vec = batch * L * H * F32                       # dt or cs
    bc = batch * L * G * N * F32                    # B or C at the group count
    states = cells * H * N * P * F32
    fwd_bytes = x + 2 * vec + 2 * bc + x + states
    bwd_bytes = (x + 2 * vec + 2 * bc + x + states  # inputs, dy, dstate
                 + x + 2 * vec + 2 * bc)            # dx, ddt, dcs, dB, dC
    return {"fwd": (fwd_flops, fwd_bytes), "bwd": (bwd_flops, bwd_bytes)}


def least_time(flops: float, nbytes: float, peak: dict) -> tuple:
    """(seconds, bound): the larger of operations over peak FLOP/s and bytes
    over peak bandwidth, and which of the two it is."""
    t_f = flops / peak["bf16_flops_per_s"]
    t_b = nbytes / peak["hbm_bytes_per_s"]
    return (t_f, "flops") if t_f >= t_b else (t_b, "bytes")

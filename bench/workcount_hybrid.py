"""Operations and bytes of the hybrid's shared attention core, from shapes.

The counts are the causal attention algorithm's, not any implementation's:
a kernel that skips the masked blocks reads as a higher share of its
roofline, never as less work.
"""
from __future__ import annotations

from workcount import F32


def shared_attn_work(cfg: dict, batch: int, seq: int) -> dict:
    """Least operations and bytes of one invocation's causal attention core
    (scores, mask, softmax, the product with v), forward and backward.

    Operations: the products over the causal half of the score matrix,
    ``S^2 / 2`` per head: forward Q K^T and P V (2 x S^2/2 x D each);
    backward dV = P^T dO, dP = dO V^T, dQ = dS K and dK = dS^T Q.
    Bytes: q, k, v and o read or written once (forward), and with them
    their cotangents (backward: q, k, v, o, dO read; dQ, dK, dV written),
    fp32.
    """
    sh = cfg["shared"]
    H, D = sh["n_heads"], sh["head_dim"]
    product = 2 * batch * H * (seq * seq / 2) * D
    tensor = batch * seq * H * D * F32
    return {"fwd": (2 * product, 4 * tensor),
            "bwd": (4 * product, 8 * tensor)}


def invocations(cfg: dict) -> int:
    k = cfg["shared"]["every"]
    return len(range(k, cfg["n_layers"], k))

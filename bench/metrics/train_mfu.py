"""train_mfu: the whole step's share of the chip's peak, in %.

Operations the forward and backward passes require per token
(``workcount.train_flops_per_token``: recomputation not counted), times the
tokens of the traced steps, over the traced window times the bf16 peak: XLA
runs fp32 dots as single bf16 passes at JAX's default precision.
"""
import workcount


def read(ctx):
    t = ctx["traffic"]
    flops = workcount.train_flops_per_token(ctx["cfg"]) \
        * t["batch"] * t["seq"] * ctx["steps"]
    window = ctx["hi"] - ctx["lo"]
    return 100.0 * flops / (window * ctx["peak"]["bf16_flops_per_s"])

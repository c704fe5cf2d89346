"""region_ms.elementwise: device time of the layers' elementwise work a
train step, in ms: the block's pre-norm and residual, the causal
convolutions with dt and A, the D skip and gated norm (regions
``block_norm``, ``mixer.conv``, ``mixer.gate``, every phase;
``regions.py``)."""
import regions


def read(ctx):
    return regions.region_ms(ctx, "block_norm", "mixer.conv", "mixer.gate")

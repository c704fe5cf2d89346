"""region_ms.shared_block: device time of the hybrid's shared transformer
block a train step, in ms: every ``shared.*`` region (the [h; e] norm,
q/k/v with adapters and rope, the attention core, Wo, the MLP, the
invocation's linear), every phase (``regions.py``).  Nothing to read in a
step without those regions."""
import regions

SHARED = ("shared.in", "shared.qkv", "shared.attn", "shared.out",
          "shared.mlp", "shared.link")


def read(ctx):
    return regions.region_ms(ctx, *SHARED) or None

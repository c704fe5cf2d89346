"""region_ms.shared_attn: device time of the shared block's attention core
(scores, mask, softmax, the product with v) a train step, in ms (region
``shared.attn``, every phase; ``regions.py``).  Nothing to read in a step
without that region."""
import regions


def read(ctx):
    return regions.region_ms(ctx, "shared.attn") or None

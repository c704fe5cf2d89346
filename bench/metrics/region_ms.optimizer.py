"""region_ms.optimizer: device time of the gradient cast, the global-norm
clip and the AdamW update a train step, in ms (region ``optimizer``;
``regions.py``)."""
import regions


def read(ctx):
    return regions.region_ms(ctx, "optimizer")

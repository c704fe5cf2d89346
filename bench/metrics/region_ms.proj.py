"""region_ms.proj: device time of the mixer's input and output
projections a train step, in ms (regions ``mixer.in_proj`` and
``mixer.out_proj``, every phase; ``regions.py``)."""
import regions


def read(ctx):
    return regions.region_ms(ctx, "mixer.in_proj", "mixer.out_proj")

"""region_ms.ssd_state: device time of the SSD's jnp inter-chunk state
scan and its output term a train step, in ms (region ``mixer.ssd_state``,
every phase; ``regions.py``)."""
import regions


def read(ctx):
    return regions.region_ms(ctx, "mixer.ssd_state")

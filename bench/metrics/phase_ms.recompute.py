"""phase_ms.recompute: device time a train step of the forward work the
backward pass recomputes under full rematerialisation, in ms (every op of
phase ``recompute``; ``regions.py``)."""
import regions


def read(ctx):
    return regions.phase_ms(ctx, "recompute")

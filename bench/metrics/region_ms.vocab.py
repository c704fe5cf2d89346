"""region_ms.vocab: device time of the embedding and the tied head (final
norm, logits, loss) a train step, in ms (regions ``embed`` and ``head``,
every phase; ``regions.py``)."""
import regions


def read(ctx):
    return regions.region_ms(ctx, "embed", "head")

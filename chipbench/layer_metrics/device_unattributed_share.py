"""Device time of events that no ``pt.`` scope reaches (XLA's own copies
between memory spaces, instructions missing from the text) over the first
chip's total, in percent: the honesty of the per-class times
(``lib/op_attribution.py``)."""
from chipbench.lib import op_attribution


def compute(ctx):
    return op_attribution.unattributed_share(ctx)

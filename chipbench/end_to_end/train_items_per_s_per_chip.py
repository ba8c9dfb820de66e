"""Items (images, tokens) trained per second per chip: the median over
the run's windows (``lib/rates.py``)."""
from chipbench.lib.rates import train_items_per_s_per_chip as compute  # noqa: F401

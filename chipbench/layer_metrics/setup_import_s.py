"""Seconds of set-up inside ``import paddle_tpu`` (the phase log's
``process/import``: first to last statement of the package's ``__init__``;
``import jax`` came before it here, and is outside).  Nothing where the
program keeps no phase log (``lib/setup_phases.py``)."""
from chipbench.lib import setup_phases


def compute(ctx):
    return setup_phases.seconds(ctx, "process/import")

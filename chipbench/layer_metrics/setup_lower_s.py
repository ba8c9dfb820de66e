"""Seconds of set-up in ``traced.lower`` of the program's steps (the
phase log's ``step/lower``, summed): jaxpr to StableHLO.  Nothing where the
program keeps no phase log (``lib/setup_phases.py``)."""
from chipbench.lib import setup_phases


def compute(ctx):
    return setup_phases.seconds(ctx, "step/lower")

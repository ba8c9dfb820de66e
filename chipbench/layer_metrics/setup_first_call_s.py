"""Seconds of set-up in the FIRST call of each compiled step (the phase
log's ``step/first_call``, summed): argument check, the executable's load,
the enqueue; host time, the device's work is waited for elsewhere.
Nothing where the program keeps no phase log (``lib/setup_phases.py``)."""
from chipbench.lib import setup_phases


def compute(ctx):
    return setup_phases.seconds(ctx, "step/first_call")

"""Seconds of set-up a cold call spent BEFORE its trace (the phase log's
``step/enter``, summed: feeds coerced, state keys, validation, the
Program's fingerprint, the step built).  Nothing where the program keeps no
phase log (``lib/setup_phases.py``)."""
from chipbench.lib import setup_phases


def compute(ctx):
    return setup_phases.seconds(ctx, "step/enter")

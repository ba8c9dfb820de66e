"""Seconds of set-up in ``jit.trace`` of the program's steps (the phase
log's ``step/trace``, summed): Python over the Program's ops.  With
``setup_lower_s`` and ``setup_xla_s`` it is ``compile_s``.  Nothing where
the program keeps no phase log (``lib/setup_phases.py``)."""
from chipbench.lib import setup_phases


def compute(ctx):
    return setup_phases.seconds(ctx, "step/trace")

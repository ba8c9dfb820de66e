"""How many step programs went through ``lowered.compile`` during set-up
(the phase log's ``step/xla`` records, cache reads included): a count, so a
rehearsal reports it too.  Nothing where the program keeps no phase log
(``lib/setup_phases.py``)."""
from chipbench.lib import setup_phases


def compute(ctx):
    return setup_phases.count(ctx, "step/xla")

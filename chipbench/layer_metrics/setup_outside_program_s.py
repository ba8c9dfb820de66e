"""``setup_s`` less the UNION of the program's phase intervals inside
set-up (overlapping phases count once): what no phase of the program
covers.  The interpreter's start and ``import jax``, libtpu's start, the
benchmark's own draws and feeds, device time somebody waited for.  The
honesty figure of the ``setup_*`` split, as ``device_unattributed_share``
is for the step.  Nothing where the program keeps no phase log
(``lib/setup_phases.py``)."""
from chipbench.lib import setup_phases


def compute(ctx):
    result = setup_phases.split(ctx)
    if result is None:
        return None
    return ctx.setup_s - result["union_s"]

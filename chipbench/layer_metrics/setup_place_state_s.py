"""Seconds of set-up in ``ShardedExecutor.place_state`` (the phase log's
``state/place``, summed): the ``device_put`` of every persistable under its
sharding.  Nothing where the program keeps no phase log
(``lib/setup_phases.py``)."""
from chipbench.lib import setup_phases


def compute(ctx):
    return setup_phases.seconds(ctx, "state/place")

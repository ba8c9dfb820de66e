"""Seconds of set-up in ``lowered.compile`` of the program's steps (the
phase log's ``step/xla``, summed): a read of JAX's persistent cache where
the record says ``cache_hit``, else an XLA compile;
``detail["setup_phases"]`` says which, per step among its longest records
and as ``xla_cache_reads`` [reads, of].  Nothing where the program keeps no
phase log (``lib/setup_phases.py``)."""
from chipbench.lib import setup_phases


def compute(ctx):
    return setup_phases.seconds(ctx, "step/xla")

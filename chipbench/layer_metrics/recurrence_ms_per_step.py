"""Device time per training step in the recurrence itself: ``rnn`` (slicing
its inputs, stacking its outputs, carrying its memories; NOT the ops of its
step block, which have scopes of their own), ``gru``, ``gru_unit``,
``lstm``, ``lstm_unit``, ``sequence_pool``; both directions, by the
innermost ``pt.`` scope (``lib/op_attribution.py``)."""
from chipbench.lib import op_attribution


def compute(ctx):
    return op_attribution.class_ms_per_step(ctx, "recurrence")

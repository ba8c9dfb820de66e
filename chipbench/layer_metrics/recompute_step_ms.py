"""Device time per training step of forward work done AGAIN in the backward
pass: the events owned by an instruction whose HLO ``op_name`` passes
through JAX's recomputation scope (``rematted_computation``, which
``jax.checkpoint`` puts around what it computes a second time;
``checkpoint`` alone is the first computation, which any program does).
What fitting the activations costs.  Nothing where the program recomputes
nothing."""
from chipbench.layer_metrics.looped_stack_step_ms import ms_per_step


def compute(ctx):
    return ms_per_step(ctx, lambda op_name: "rematted_computation" in op_name)

"""Tracing, lowering and compiling during set-up that no step program
accounts for: the seeded draws, ``device_put``s and ``jnp`` glue.  The
program sums JAX's own duration events (``jax_trace_s`` + ``jax_lower_s`` +
``jax_backend_compile_s`` in ``compile_stats()``, read at the end of
set-up) and leaves out the events fired while a step compiles (those are
``setup_trace_s`` / ``setup_lower_s`` / ``setup_xla_s``'s) and what an
event nested in another would count twice: seconds somebody waited, so
together with the step phases it fits inside set-up.  Nothing where the
program sums no such seconds."""
from chipbench.lib import setup_phases


def compute(ctx):
    return setup_phases.jax_seconds(ctx)

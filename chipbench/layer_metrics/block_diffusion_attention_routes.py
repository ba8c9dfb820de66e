"""How many times a ``block_diffusion_attention`` op was lowered while the
cell's programs were traced, by either route (``route/
block_diffusion_attention:{kernel,xla}`` in ``profiler.compile_stats()``
summed, at the end of set-up): engagement of the block-masked attention,
read, not assumed.  Nothing where the program counts no such route.  WHICH
route, and the kernels' own (``route/flash_attention_bwd:{one_pass,
two_pass}`` beside ``route/flash_attention_bwd:block_diffusion``), is in
``detail["routes"]`` of a traced run."""


def compute(ctx):
    counters = ctx.before["compile"]
    found = [counters[k] for k in ("route/block_diffusion_attention:kernel",
                                   "route/block_diffusion_attention:xla")
             if k in counters]
    return sum(found) if found else None

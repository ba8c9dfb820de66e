"""The profiler around a short slice of the window (traced runs only)."""
from __future__ import annotations

import glob
import os
import shutil


def trace_dir(ctx) -> str:
    """A fixed place inside the checkout, git-ignored by
    chipbench/.gitignore."""
    return os.path.join(ctx.root, "chipbench", "out", ctx.args.workload,
                        "trace")


def start(ctx):
    import jax

    shutil.rmtree(trace_dir(ctx), ignore_errors=True)
    os.makedirs(trace_dir(ctx), exist_ok=True)
    # the host's Python frames are not read by any metric and are most of
    # a trace's size and of tracing's cost; cb:/pt: annotations stay
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.enable_hlo_proto = False
    jax.profiler.start_trace(trace_dir(ctx), profiler_options=options)


def stop(ctx, steps=None, window_span="cb:window"):
    """Stop the profiler and reduce what it wrote into ``ctx.trace``."""
    import jax

    from . import trace_reduce

    jax.profiler.stop_trace()
    found = sorted(glob.glob(os.path.join(
        trace_dir(ctx), "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise RuntimeError(f"the profiler wrote no trace under "
                           f"{trace_dir(ctx)}")
    ctx.trace = trace_reduce.summarize(
        trace_reduce.from_xplane(found[-1]), n_devices=len(ctx.devices),
        steps=steps, window_span=window_span)

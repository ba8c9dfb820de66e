"""Driver ``serve_open_loop``: single-example requests on a seeded
schedule against ``serving.Server`` with its defaults.

The per-call path users of ``Server`` take: host rows -> ``stack_feeds`` /
``pad_batch`` -> ``Executor.run`` -> device -> split.  Every bucket is
warmed during set-up; requests are images from a seeded pool, so every
answer can be checked against a table.

Cell parameters: ``rate_per_s`` (a fixed number, found once by a sweep on
the chip), ``pool``, ``check_batch``, ``trace_seconds``.
"""
from __future__ import annotations

import time

import numpy as np

from chipbench.lib import open_loop, profile, weights

# served answer against the same program run directly on the pool, as
# |log p - log p'|: the same arithmetic in bfloat16, where the bucket a
# request lands in only changes the tiling.  No request of the 27 000 that
# PR 22 served on the chip differed by more; the float32 reference is held
# to INFER_LOGP_TOL beside it.
SERVED_LOGP_TOL = 0.05


def _model(ctx, exe, main, output, scope, example, dispatch_ms):
    from paddle_tpu.serving import Model

    if not ctx.tracing:
        return Model.from_program(exe, main, [output], scope=scope,
                                  name="resnet50", example=example)

    # traced run: the same call, inside a cb:model_fn span and drained, so
    # that a dispatch's host-to-device-to-host time can be read
    import jax

    def fn(feeds):
        t0 = time.perf_counter()
        with ctx.span("model_fn"):
            outs = exe.run(main, feed=feeds, fetch_list=[output],
                           scope=scope, return_numpy=False, is_test=True)
            jax.block_until_ready(outs)
        dispatch_ms.append((time.perf_counter() - t0) * 1e3)
        return outs

    return Model("resnet50", fn, output_names=[output], example=example)


def run(ctx) -> dict:
    import paddle_tpu as pt
    from paddle_tpu.serving import Server

    cell = ctx.cell
    built = ctx.config.build("infer", 1, ctx.sizes)
    main, output = built["main"], built["output"]
    exe = pt.Executor(amp=built["amp"], observe=True if ctx.tracing else None)
    exe.run(built["startup"], feed={}, fetch_list=[])
    scope = pt.global_scope()
    weights.reseed(scope, weights.seeder(main), ctx.seed_for("weights"))

    (name, spec), = built["feeds"].items()
    pool = np.random.default_rng(ctx.seed_for("pool")).standard_normal(
        (cell["pool"],) + tuple(spec["shape"]), dtype=np.float32)
    dispatch_ms: list = []
    srv = Server()                               # the defaults, all of them
    srv.warmup_buckets = list(srv.buckets)       # ... and every bucket warm
    srv.add_model(_model(ctx, exe, main, output, scope, {name: pool[0]},
                         dispatch_ms))
    srv.start()
    # Server.start() warms each bucket's executable; a first request per
    # bucket still compiles the programs that split a batch's answers into
    # rows, so one burst per bucket size goes through the whole path here
    for size in srv.buckets:
        burst = [srv.submit({name: pool[i % cell["pool"]]}, deadline_ms=None)
                 for i in range(size)]
        for pending in burst:
            pending.result(timeout=300.0)
    del dispatch_ms[:]                           # warm-up calls do not count
    schedule = open_loop.poisson_schedule(
        ctx.seed_for("arrivals"), cell["rate_per_s"], ctx.args.seconds)
    picks = np.random.default_rng(ctx.seed_for("picks")).integers(
        0, cell["pool"], len(schedule))
    ctx.end_setup()

    health0 = srv.health()["models"]["resnet50"]
    tracer = None
    if ctx.tracing:
        # the profiler runs over the last trace_seconds of the window; it
        # is started from a timer so that the sender is never held up
        import threading
        start_at = max(0.0, ctx.args.seconds - cell["trace_seconds"])
        tracer = threading.Timer(start_at, profile.start, args=(ctx,))
        tracer.start()
    with ctx.span("window"):
        records = open_loop.run(
            schedule, lambda i: srv.submit({name: pool[picks[i]]}))
    health1 = srv.health()["models"]["resnet50"]
    ctx.end_window()
    if tracer is not None:
        tracer.join()
        profile.stop(ctx, window_span="cb:model_fn")
    srv.shutdown(drain=True, timeout=60.0)

    # every answer against the direct batched forward of the same program
    # (the largest bucket's executable: no new compile)
    step = srv.buckets[-1]
    table = np.concatenate([
        np.asarray(exe.run(main, feed={name: pool[i:i + step]},
                           fetch_list=[output], scope=scope,
                           is_test=True)[0], np.float32)
        for i in range(0, cell["pool"], step)])
    log_table = np.log(np.maximum(table, 1e-30))
    wrong, served_err = 0, 0.0
    for rec in records:
        if rec.error is None and rec.outputs is not None:
            got = np.log(np.maximum(
                np.asarray(rec.outputs[0], np.float32), 1e-30))
            err = float(np.max(np.abs(got - log_table[picks[rec.index]])))
            served_err = max(served_err, err)
            if not err <= SERVED_LOGP_TOL:
                rec.error = "WrongAnswer"
                wrong += 1
        rec.outputs = None
    # ... and the sample's log-probabilities against the float32 reference
    n = cell["check_batch"]
    params = {k: np.asarray(scope.get(k)) for k in scope.keys()}
    ref = np.asarray(ctx.config.reference("infer", params,
                                          {name: pool[:n]}, ctx.sizes))
    logp_err = float(np.max(np.abs(log_table[:n] - ref)))
    tol = ctx.config.INFER_LOGP_TOL

    errors: dict = {}
    for rec in records:
        key = rec.error or ("ok" if rec.done is not None else "unanswered")
        errors[key] = errors.get(key, 0) + 1
    failed = len(records) - errors.get("ok", 0)
    compiles = ctx.compiles_in_window
    ctx.obs.update(
        records=records, dispatch_ms=dispatch_ms,
        served=health1["served"] - health0["served"],
        batches=health1["batches"] - health0["batches"])
    ctx.detail.update(outcomes=errors, wrong=wrong, logp_err=logp_err,
                      served_logp_err=served_err,
                      offered_per_s=cell["rate_per_s"],
                      compiles_in_window=compiles,
                      allocator_peak_bytes=ctx.allocator_peak_bytes)
    return {"correct": wrong == 0 and logp_err <= tol and compiles == 0,
            "attempted": len(records), "failed": failed}

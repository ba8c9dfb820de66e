"""Driver ``train_scan``: a device-resident seeded batch trained by
``Executor.run_steps(K)`` back to back.

A window is ONE compiled dispatch of K steps (a ``lax.scan`` with donated
state) ended by reading the K losses to the host, so per-step host work is
out of the measurement by construction; this is ``bench.py``'s pinned
timing core (PERF.md: window spread 0.03 %), run for ``--seconds``.  With
``mesh`` in the cell the same program runs under a ``ShardedExecutor`` on
feeds already split over ``dp``.

Cell parameters: ``batch_per_chip``, ``steps_per_window``, ``check_batch``,
``trace_windows``, ``check_window_loss`` (hold the window's first loss to
the reference's forward pass on the whole window batch: only where one
chip can hold that pass), optional ``mesh`` ({"dp": 4}).
"""
from __future__ import annotations

import time

import numpy as np

from chipbench.lib import check, device, profile, weights


def _executor(ctx, built):
    """(executor, sharding of a batch-leading array or None, replicated
    sharding or None)."""
    import paddle_tpu as pt

    observe = True if ctx.tracing else None    # the pt: annotations
    mesh_axes = ctx.cell.get("mesh")
    if not mesh_axes:
        return pt.Executor(amp=built["amp"], observe=observe), None, None
    from jax.sharding import NamedSharding, PartitionSpec as P

    from paddle_tpu.parallel import ShardedExecutor, mesh_for_axes

    mesh = mesh_for_axes(mesh_axes, devices=ctx.devices)
    exe = ShardedExecutor(mesh=mesh, batch_axis="dp", amp=built["amp"],
                          observe=observe)
    return exe, NamedSharding(mesh, P("dp")), NamedSharding(mesh, P())


class _Start:
    """The seeded start every check returns to: the startup program's
    state with the seeded draw over it."""

    def __init__(self, ctx, exe, built, replicated):
        self.ctx, self.exe, self.built = ctx, exe, built
        self.replicated = replicated
        self.draw = weights.seeder(built["main"], replicated)

    def restore(self):
        import paddle_tpu as pt

        self.exe.run(self.built["startup"], feed={}, fetch_list=[])
        self.ctx.mark("startup")
        weights.reseed(pt.global_scope(), self.draw,
                       self.ctx.seed_for("weights"))
        if self.replicated is not None:
            self.exe.place_state(self.built["main"])
        self.ctx.mark("reseeded")

    def state(self):
        """Host copies of the program's persistable state (a step donates
        the device's)."""
        import paddle_tpu as pt

        scope, block = pt.global_scope(), self.built["main"].global_block()
        return {n: np.asarray(scope.get(n)) for n in scope.keys()
                if block.has_var(n) and block.var(n).persistable}


def _check_against_reference(ctx, exe, built, start, batch_sharding, feeds,
                             first_loss):
    """The system against the float32 reference, from the seeded start:
    every entry of the configuration's ``CHECKS`` on a seeded sample batch
    and, where the cell asks for it, the window's first loss on the whole
    window batch."""
    main, sizes = built["main"], ctx.sizes
    names = list(sizes["check_params"])
    results = {}
    start.restore()
    params = start.state()
    if ctx.cell.get("check_window_loss"):      # reads the state, moves none
        ref = float(ctx.config.reference(
            "loss", params,
            {k: np.asarray(v) for k, v in feeds.items()}, sizes))
        err = abs(first_loss - ref) / abs(ref)
        results["window_first_loss"] = {
            "ok": bool(err <= ctx.config.WINDOW_LOSS_REL_TOL),
            "loss_rel_err": err}
        ctx.mark("checked_window_loss")
    for i, spec in enumerate(ctx.config.CHECKS):
        if i:                                  # the last step moved the state
            start.restore()
            params = start.state()
        sample = weights.make_feeds(built["feeds"], ctx.cell["check_batch"],
                                    ctx.seed_for("sample"), batch_sharding)
        got = exe.run(main, feed=sample, is_test=spec["is_test"],
                      fetch_list=[built["loss"]]
                      + [f"{n}@GRAD" for n in names])
        ctx.mark(f"stepped_{spec['name']}")
        ref_loss, ref_grads = ctx.config.reference(
            "train", params, {k: np.asarray(v) for k, v in sample.items()},
            sizes, frozen_stats=spec["is_test"])
        results[spec["name"]] = check.compare_training(
            spec, got[0], dict(zip(names, got[1:])), ref_loss, ref_grads)
    return results


def run(ctx) -> dict:
    cell = ctx.cell
    chips = cell["chips"]
    batch = cell["batch_per_chip"] * chips
    k = cell["steps_per_window"]
    built = ctx.config.build("train", batch, ctx.sizes)
    main, loss = built["main"], built["loss"]
    exe, batch_sharding, replicated = _executor(ctx, built)
    ctx.mark("built")

    start = _Start(ctx, exe, built, replicated)
    start.restore()
    feeds = weights.make_feeds(built["feeds"], batch, ctx.seed_for("batch"),
                               batch_sharding)
    ctx.mark("seeded")

    def window():
        t0 = time.perf_counter()
        (lv,) = exe.run_steps(k, main, feed=feeds, fetch_list=[loss],
                              return_numpy=False)
        losses = np.asarray(lv)               # the barrier: window done
        return time.perf_counter() - t0, losses

    # warm-up of this cell's own shapes: the K-step scan and nothing else
    ctx.program_bytes = device.program_bytes(
        exe.compile(main, feed=feeds, fetch_list=[loss], num_steps=k))
    ctx.mark("compiled")
    warm = []
    for _ in range(4):          # a mesh step may specialize a second time
        seen = ctx.compile_count()
        warm.append(window())
        if ctx.compile_count() == seen:
            break
    ctx.end_setup()

    windows, losses = [], []
    trace_windows = cell["trace_windows"] if ctx.tracing else 0
    if trace_windows:
        profile.start(ctx)
    t_end = time.perf_counter() + ctx.args.seconds
    while True:
        traced = len(windows) < trace_windows
        with ctx.span("window"):
            seconds, lv = window()
        windows.append({"steps": k, "seconds": seconds, "traced": traced})
        losses.append(lv)
        if traced and len(windows) == trace_windows:
            profile.stop(ctx, steps=k * trace_windows)
        if not traced and time.perf_counter() >= t_end:
            break
    ctx.end_window()
    ctx.mark("window")
    ctx.obs.update(windows=windows, chips=chips,
                   items_per_step=batch * built["items_per_example"])

    losses = np.concatenate(losses)
    fall = check.losses_fall(losses)
    agree = _check_against_reference(ctx, exe, built, start, batch_sharding,
                                     feeds, float(warm[0][1][0]))
    ctx.mark("checked")
    compiles = ctx.compiles_in_window
    ctx.detail.update(
        warmup_windows_s=[w[0] for w in warm], windows=len(windows),
        window_s=[w["seconds"] for w in windows[:64]],
        loss_first=fall["first"], loss_last=fall["last"],
        reference=agree, compiles_in_window=compiles,
        program_bytes=ctx.program_bytes,
        allocator=ctx.devices[0].memory_stats())
    return {"correct": fall["ok"] and compiles == 0
            and all(r["ok"] for r in agree.values()),
            "attempted": int(losses.size),
            "failed": int(np.sum(~np.isfinite(losses)))}

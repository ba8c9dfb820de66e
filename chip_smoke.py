"""chip_smoke.py — does today's code start on the chip?

Drives the main path once, in ONE process, through the entry points a user
calls, on seeded synthetic data (no network, no git):

  env                    versions, devices, HBM limit, dispatch round trip,
                         whether block_until_ready() waits for the device
  resnet50_train         ResNet-50 / 1000 classes / 3x224x224 / batch 128 /
                         Momentum / Executor(amp=True) — bench.py's program:
                         startup, 3 Executor.run steps fed from host rows
                         through DataFeeder, then one run_steps(20) window
                         on device-resident feeds
  serve_resnet50         save_inference_model of that trained program ->
                         fresh scope -> serving.Model -> Server.start() ->
                         8 single-image requests -> shutdown()
  flash_attention_train  layers.flash_attention in a Program through
                         Executor, causal bf16, 8 heads: T=2048 forward +
                         gradients vs the float32 reference at head dims 64
                         and 128, then one T=65536 training step (fits only
                         if the O(T) Pallas kernel really ran)

Every leg prints one JSON line naming platform / device_kind / device count,
its compile seconds and peak_bytes_in_use.  Any failing assertion or raised
error ends the run non-zero: no try/except around a leg.  The LAST stdout
line is {"ok": true, "device": {...}} with the device as JAX reports it.

Without a ``tpu`` default backend the script refuses to run (exit 2, no
result line).  The one CPU mode is the pre-flight: ``python3 chip_smoke.py
--cpu-preflight`` with JAX_PLATFORMS=cpu — sizes shrink, ResNet depth is
cut to 18, Pallas runs with interpret=True, and every line says
``"platform": "cpu"``.  It checks control flow before chip time is spent;
it is not a result.
"""
from __future__ import annotations

import json
import os
import sys
import tempfile
import time

import numpy as np

PREFLIGHT_FLAG = "--cpu-preflight"


def _sizes(preflight: bool) -> dict:
    if preflight:
        return dict(depth=18, classes=10, image=32, batch=4, window=3,
                    attn_t=256, long_t=512, interpret=True)
    return dict(depth=50, classes=1000, image=224, batch=128, window=20,
                attn_t=2048, long_t=65536, interpret=False)


class _Run:
    """Shared facts + the per-leg JSON line."""

    def __init__(self, jax, preflight: bool):
        from paddle_tpu import profiler
        self.jax = jax
        self.dev = jax.devices()[0]
        self.device = {"platform": self.dev.platform,
                       "kind": self.dev.device_kind,
                       "count": len(jax.devices())}
        self.preflight = preflight
        self.size = _sizes(preflight)
        self.stats = profiler.compile_stats()
        self._compile_s = 0.0
        self._cache_hits = 0

    def emit(self, leg: str, **fields):
        """One JSON line per leg; compile seconds and persistent-cache hits
        are the deltas since the previous leg."""
        total = self.stats.total_compile_seconds()
        hits = self.stats.snapshot().get("jax_cache_hits", 0)
        mem = self.dev.memory_stats() or {}
        line = {"leg": leg, "platform": self.device["platform"],
                "device_kind": self.device["kind"],
                "device_count": self.device["count"],
                "compile_s": round(total - self._compile_s, 3),
                "jax_cache_hits": hits - self._cache_hits,
                "peak_bytes_in_use": mem.get("peak_bytes_in_use"),
                **fields}
        self._compile_s, self._cache_hits = total, hits
        print(json.dumps(line), flush=True)

    def on_device(self, x) -> bool:
        return all(d.platform == self.device["platform"]
                   for d in x.devices())


# ---------------------------------------------------------------------------
# leg: env
# ---------------------------------------------------------------------------
def leg_env(run: _Run):
    jax = run.jax
    import jax.numpy as jnp
    import jaxlib
    from jax import lax

    from paddle_tpu.core import compile_cache
    from paddle_tpu.native import get_native

    from importlib import metadata
    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:    # a CPU-only installation
        libtpu = None

    # round trip of a trivial jitted call: dispatch + wait, one at a time
    f = jax.jit(lambda x: x + 1.0)
    x = jnp.zeros((8, 128), jnp.float32)
    f(x).block_until_ready()
    trips = []
    for _ in range(200):
        t0 = time.perf_counter()
        f(x).block_until_ready()
        trips.append(time.perf_counter() - t0)
    # ... and enqueue-only cost: 200 chained calls, one wait at the end
    t0 = time.perf_counter()
    y = x
    for _ in range(200):
        y = f(y)
    y.block_until_ready()
    chained = (time.perf_counter() - t0) / 200

    # does block_until_ready() return only after a long scan drains?  If it
    # does, a scalar fetch right after it is instant, and a scalar fetch in
    # its place takes as long.
    n = 64 if run.preflight else 2048
    iters = 8 if run.preflight else 1500
    w = (jnp.eye(n, dtype=jnp.bfloat16) * 0.5)

    @jax.jit
    def long_scan(a):
        def body(c, _):
            return jnp.dot(c, w, preferred_element_type=jnp.bfloat16), None
        return lax.scan(body, a, None, length=iters)[0]

    a = jnp.ones((n, n), jnp.bfloat16)
    float(long_scan(a)[0, 0])                       # compile + warm
    t0 = time.perf_counter()
    out = long_scan(a)
    t_enqueue = time.perf_counter() - t0
    out.block_until_ready()
    t_bur = time.perf_counter() - t0
    t1 = time.perf_counter()
    float(out[0, 0])
    t_fetch_after = time.perf_counter() - t1
    t0 = time.perf_counter()
    float(long_scan(a)[0, 0])
    t_scalar_fetch = time.perf_counter() - t0
    drains = t_fetch_after < 0.1 * t_bur and t_bur > 0.5 * t_scalar_fetch

    mem = run.dev.memory_stats() or {}
    run.emit(
        "env", jax=jax.__version__, jaxlib=jaxlib.__version__, libtpu=libtpu,
        python=sys.version.split()[0],
        default_backend=jax.default_backend(),
        devices=[str(d) for d in jax.devices()],
        hbm_bytes_limit=mem.get("bytes_limit"),
        trivial_jit_round_trip_us={
            "median": round(float(np.median(trips)) * 1e6, 1),
            "p90": round(float(np.percentile(trips, 90)) * 1e6, 1)},
        trivial_jit_chained_us_per_call=round(chained * 1e6, 1),
        long_scan={"iters": iters, "n": n,
                   "enqueue_s": round(t_enqueue, 5),
                   "block_until_ready_s": round(t_bur, 5),
                   "scalar_fetch_after_s": round(t_fetch_after, 5),
                   "scalar_fetch_instead_s": round(t_scalar_fetch, 5)},
        block_until_ready_drains=bool(drains),
        compile_cache_dir=compile_cache.cache_dir(),
        compile_cache_dir_from_env=bool(
            os.environ.get("JAX_COMPILATION_CACHE_DIR")),
        native_feeder_loaded=get_native() is not None)
    assert drains or run.preflight, \
        "block_until_ready() returned before the device drained"


# ---------------------------------------------------------------------------
# leg: resnet50_train (+ serve_resnet50, which serves what it trained)
# ---------------------------------------------------------------------------
def leg_resnet50_train(run: _Run):
    jax = run.jax
    import paddle_tpu as pt
    from paddle_tpu import layers, models

    s = run.size
    B, S, C = s["batch"], s["image"], s["classes"]
    pt.core.reset_default_programs()
    pt.core.reset_global_scope()
    pt.unique_name.reset()

    # bench.py's program
    img = layers.data("img", shape=[3, S, S], dtype="float32")
    label = layers.data("label", shape=[1], dtype="int64")
    pred = models.resnet_imagenet(img, num_classes=C, depth=s["depth"])
    loss = layers.mean(layers.cross_entropy(pred, label))
    pt.optimizer.Momentum(learning_rate=0.01 / B, momentum=0.9) \
        .minimize(loss)

    exe = pt.Executor(amp=True)
    exe.run(pt.default_startup_program(), feed={}, fetch_list=[])
    prog = pt.default_main_program()

    # host rows -> DataFeeder (pad/stack) -> device: the README quick start
    rng = np.random.RandomState(0)
    rows = [(rng.rand(3, S, S).astype("float32"), int(rng.randint(0, C)))
            for _ in range(B)]
    feeder = pt.DataFeeder([img, label])
    losses, first_s = [], None
    for i in range(3):
        t0 = time.perf_counter()
        (lv,) = exe.run(prog, feed=feeder.feed(rows), fetch_list=[loss],
                        return_numpy=False)
        assert run.on_device(lv), f"fetch not on device: {lv.devices()}"
        losses.append(float(lv))
        if i == 0:
            first_s = time.perf_counter() - t0

    # device-resident feeds -> one compiled K-step window (what bench.py
    # times)
    feeds = {k: jax.device_put(v) for k, v in feeder.feed(rows).items()}
    K = s["window"]
    t0 = time.perf_counter()
    (lv,) = exe.run_steps(K, prog, feed=feeds, fetch_list=[loss],
                          return_numpy=False)
    assert run.on_device(lv), f"fetch not on device: {lv.devices()}"
    window = np.asarray(lv)
    window_first_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    (lv,) = exe.run_steps(K, prog, feed=feeds, fetch_list=[loss],
                          return_numpy=False)
    window2 = np.asarray(lv)
    window_warm_s = time.perf_counter() - t0
    losses += [float(v) for v in window] + [float(v) for v in window2]

    assert window.shape == (K,), window.shape
    assert np.all(np.isfinite(losses)), losses
    assert losses[-1] < losses[0], \
        f"loss on the repeated batch did not fall: {losses}"
    # startup + run variant + run_steps variant, each traced exactly once
    assert run.stats.snapshot().get("traces", 0) == 3, run.stats.report()
    run.stats.assert_no_retrace()

    run.emit("resnet50_train", depth=s["depth"], classes=C, image=S,
             batch=B, amp=True,
             loss_first=round(losses[0], 4), loss_last=round(losses[-1], 4),
             steps=len(losses),
             first_run_s=round(first_s, 3),
             first_window_s=round(window_first_s, 3),
             warm_window_s=round(window_warm_s, 4),
             warm_window_img_per_s_observed=round(B * K / window_warm_s, 1),
             traces=run.stats.snapshot().get("traces", 0))
    return exe, prog, pred, rows


def leg_serve_resnet50(run: _Run, exe, prog, pred, rows):
    import paddle_tpu as pt
    from paddle_tpu.serving import Model, Server

    n_req = 8
    images = [r[0] for r in rows[:n_req]]
    while len(images) < n_req:                      # pre-flight batch < 8
        images.append(images[len(images) % len(rows)])

    with tempfile.TemporaryDirectory(prefix="pt_smoke_model_") as d:
        pt.io.save_inference_model(d, ["img"], [pred], exe,
                                   main_program=prog)
        scope = pt.core.Scope()                     # fresh: nothing trained
        infer_exe = pt.Executor(amp=True)
        iprog, feed_names, fetch_vars = pt.io.load_inference_model(
            d, infer_exe, scope=scope)
    assert feed_names == ["img"], feed_names

    model = Model.from_program(infer_exe, iprog, fetch_vars, scope=scope,
                               name="resnet50", example={"img": images[0]})
    srv = Server(max_batch=n_req, max_wait_ms=5.0, deadline_ms=None,
                 warmup_buckets=[1, 2, 4, 8])
    srv.add_model(model)
    traces_before = run.stats.snapshot().get("traces", 0)
    t0 = time.perf_counter()
    srv.start()
    warmup_s = time.perf_counter() - t0
    traces_ready = run.stats.snapshot().get("traces", 0)
    try:
        pending = [srv.submit({"img": im}) for im in images]
        answers = [p.result(timeout=300.0) for p in pending]
    finally:
        srv.shutdown()
    traces_done = run.stats.snapshot().get("traces", 0)
    # reference: the same eight images as ONE direct Executor batch
    (ref,) = infer_exe.run(iprog, feed={"img": np.stack(images)},
                           fetch_list=fetch_vars, scope=scope, is_test=True)
    ref = np.asarray(ref, np.float32)

    assert len(answers) == n_req
    got = np.stack([np.asarray(a[0], np.float32) for a in answers])
    assert got.shape == (n_req, run.size["classes"]), got.shape
    assert np.all(np.isfinite(got))
    err = float(np.max(np.abs(got - ref)))
    assert err < 2e-2, f"served answers differ from the direct batch: {err}"
    # warm-up compiled every bucket before the first request: none compiled
    # while requests were in flight
    assert traces_ready - traces_before == len(srv.warmup_buckets), \
        (traces_before, traces_ready)
    assert traces_done == traces_ready, (traces_ready, traces_done)
    assert srv.state == "stopped", srv.state

    run.emit("serve_resnet50", requests=n_req, answers=len(answers),
             answer_shape=list(got.shape[1:]),
             warmup_buckets=srv.warmup_buckets,
             warmup_s=round(warmup_s, 3),
             max_abs_err_vs_direct_batch=round(err, 6))


# ---------------------------------------------------------------------------
# leg: flash_attention_train
# ---------------------------------------------------------------------------
HEADS = 8


def _attention_program(T, D, interpret, with_probe):
    """q/k/v as parameters [HEADS, T, D] (benchmark/longctx.py --framework's
    program); returns (exe, prog, loss, out var)."""
    import paddle_tpu as pt
    from paddle_tpu import layers

    pt.core.reset_default_programs()
    pt.core.reset_global_scope()
    pt.unique_name.reset()
    helper = pt.layer_helper.LayerHelper("smoke_attn")
    q, k, v = (helper.create_parameter(
        pt.ParamAttr(name=f"attn_{n}",
                     initializer=pt.initializer.Normal(0.0, 1.0)),
        shape=[HEADS, T, D], dtype="float32") for n in "qkv")
    o = layers.flash_attention(q, k, v, causal=True, interpret=interpret)
    if with_probe:
        # sum(o * w): the cotangent reaching the kernel is w itself
        w = layers.data("w", shape=[HEADS, T, D], dtype="float32",
                        append_batch_size=False)
        loss = layers.reduce_sum(layers.elementwise_mul(o, w))
    else:
        loss = layers.scale(layers.mean(layers.elementwise_mul(o, o)), 1e-3)
    pt.optimizer.SGD(learning_rate=1e-6).minimize(loss)
    exe = pt.Executor(amp=True)
    exe.run(pt.default_startup_program(), feed={}, fetch_list=[])
    return exe, pt.default_main_program(), loss, o


def _check_against_reference(run: _Run, T, D):
    jax = run.jax
    import jax.numpy as jnp

    import paddle_tpu as pt
    from paddle_tpu.ops.pallas_kernels import _reference_attention

    exe, prog, loss, o = _attention_program(T, D, run.size["interpret"],
                                            with_probe=True)
    scope = pt.core.global_scope()
    # what the kernel sees under amp: the bf16 rounding of the fp32 masters
    qkv = [jnp.asarray(scope.find_var(f"attn_{n}")).astype(jnp.bfloat16)
           .astype(jnp.float32) for n in "qkv"]
    w = np.random.RandomState(1).randn(HEADS, T, D).astype("float32")
    w = np.asarray(jnp.asarray(w).astype(jnp.bfloat16).astype(jnp.float32))

    got = exe.run(prog, feed={"w": w},
                  fetch_list=[o, "attn_q@GRAD", "attn_k@GRAD",
                              "attn_v@GRAD"], return_numpy=False)
    assert all(run.on_device(g) for g in got)

    def ref_loss(q, k, v):
        out = _reference_attention(q, k, v, True, D ** -0.5)
        return jnp.sum(out * w), out

    with jax.default_matmul_precision("highest"):
        (_, ref_o), ref_g = jax.value_and_grad(
            ref_loss, argnums=(0, 1, 2), has_aux=True)(*qkv)
    errs = {}
    for name, g, r in zip(("out", "dq", "dk", "dv"), got,
                          (ref_o,) + tuple(ref_g)):
        g = np.asarray(g, np.float32)
        r = np.asarray(r, np.float32)
        assert g.shape == r.shape, (name, g.shape, r.shape)
        assert np.all(np.isfinite(g)), name
        errs[name] = float(np.max(np.abs(g - r)) / np.max(np.abs(r)))
    # bf16 outputs of an f32-accumulating kernel: 2^-8 relative per
    # element, a few ulps after the reductions
    assert max(errs.values()) < 3e-2, errs
    return {k: round(v, 5) for k, v in errs.items()}


def leg_flash_attention_train(run: _Run):
    s = run.size
    route = "interpret" if s["interpret"] else "pallas"
    before = run.stats.snapshot()
    errs = {f"d{D}": _check_against_reference(run, s["attn_t"], D)
            for D in (64, 128)}

    # the long step: O(T^2) scores would need HEADS * T^2 * 4 bytes
    T = s["long_t"]
    exe, prog, loss, _ = _attention_program(T, 64, s["interpret"],
                                            with_probe=False)
    t0 = time.perf_counter()
    (l0,) = exe.run(prog, feed={}, fetch_list=[loss], return_numpy=False)
    l0 = float(l0)
    first_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    (l1,) = exe.run(prog, feed={}, fetch_list=[loss], return_numpy=False)
    assert run.on_device(l1)
    l1 = float(l1)
    step_s = time.perf_counter() - t0
    assert np.isfinite(l0) and np.isfinite(l1), (l0, l1)

    after = run.stats.snapshot()
    routes = {k.split(":", 1)[1]: after.get(k, 0) - before.get(k, 0)
              for k in after if k.startswith("route/flash_attention:")}
    # three programs traced, each routing flash_attention exactly once —
    # and never to one of the other two implementations
    assert routes == {route: 3}, routes
    run.stats.assert_no_retrace()

    run.emit("flash_attention_train", heads=HEADS, causal=True,
             dtype="bfloat16", routes=routes,
             ref_tokens=s["attn_t"], rel_err_vs_f32_reference=errs,
             long_tokens=T, long_loss=[float(f"{l0:.4g}"), float(f"{l1:.4g}")],
             dense_scores_bytes_avoided=HEADS * T * T * 4,
             long_first_step_s=round(first_s, 3),
             long_step_s_observed=round(step_s, 4))


# ---------------------------------------------------------------------------
def main(argv) -> int:
    preflight = PREFLIGHT_FLAG in argv
    import jax

    backend = jax.default_backend()
    if preflight and backend != "cpu":
        print(f"chip_smoke: {PREFLIGHT_FLAG} needs JAX_PLATFORMS=cpu "
              f"(default backend here: {backend!r})", file=sys.stderr)
        return 2
    if not preflight and backend != "tpu":
        print(f"chip_smoke: needs a TPU; JAX's default backend here is "
              f"{backend!r} ({jax.devices()[0].device_kind}).  The only "
              f"CPU mode is `JAX_PLATFORMS=cpu python3 chip_smoke.py "
              f"{PREFLIGHT_FLAG}`.", file=sys.stderr)
        return 2

    import paddle_tpu  # noqa: F401  (a bare directory fails here)

    run = _Run(jax, preflight)
    leg_env(run)
    trained = leg_resnet50_train(run)
    leg_serve_resnet50(run, *trained)
    del trained
    leg_flash_attention_train(run)

    # every dispatch ran the executable the telemetry recorded
    assert run.stats.snapshot().get("lazy_jit_fallbacks", 0) == 0
    final = {"ok": True, "device": run.device}
    if preflight:
        final["preflight"] = True
    print(json.dumps(final), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))

"""Headline benchmark: ResNet-50 training throughput, images/sec/chip,
plus the seq2seq+attention tokens/s north-star (BASELINE.json).

``bench.py --mesh dp=8 [--simulate]`` runs the multi-chip leg instead: the
auto-sharding planner (paddle_tpu.analysis.planner) proposes specs for the
mesh, a ``ShardedExecutor(auto_shard=True)`` executes one training step
with them, and the fetches are checked against an unsharded step — the
planner-proposed-specs smoke row for MULTICHIP_*.json.  ``--simulate``
forces the virtual-device CPU platform
(``--xla_force_host_platform_device_count``): a CPU leg BY NAME, whose row
says ``"platform": "cpu"`` and carries no rate.

Every other invocation measures the chip and refuses to start without one:
when JAX's default backend is not ``tpu`` the script exits non-zero naming
the platform it found, and prints no metric.  A leg that raises ends the
run non-zero; nothing is swallowed.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline",
"platform", "device_kind", "device_count"} for the headline metric, with
the seq2seq number carried in "extra_metrics" on the same line (the driver
records the whole object).

Methodology (pinned, round 4 — see benchmark/RESULTS.md "Methodology"):
- Each timed window is ONE compiled dispatch: Executor.run_steps(K)
  compiles lax.scan over K training steps with donated state, so the
  per-step host dispatch rate is out of the measurement (and out of the
  training loop — run_steps is the user-facing API).  Reading the stacked
  losses is the window barrier; the first call is compile + warmup.
- Median of N windows with the (max-min)/median spread reported.

Baselines: the reference's best published ResNet-50 *training* number is
82.35 img/s (batch 128) on a 2x20-core Skylake with MKL-DNN
(benchmark/IntelOptimizedPaddle.md:39-45 — no GPU ResNet-50 number exists
in-repo; BASELINE.md "Gaps").  vs_baseline = ours / 82.35.  The reference
never published a seq2seq tokens/s number (BASELINE.md "Gaps"), so that
metric's vs_baseline is null — this framework's own measurement IS the
baseline going forward.
"""
from __future__ import annotations

import json
import statistics
import sys
import time

import numpy as np

BASELINE_IMG_S = 82.35
BATCH = 128


def _median_window_throughput(exe, prog, feeds, loss, units_per_step,
                              iters, reps):
    """Pinned timing core (round 4): each window is ONE compiled dispatch
    of ``iters`` steps (`Executor.run_steps` — a device-side lax.scan with
    donated state), so per-step host dispatch is out of the measurement
    entirely; the first (untimed) call is the compile + warmup.  Median of
    `reps` windows; spread = (max-min)/median."""
    t0 = time.perf_counter()
    (lv,) = exe.run_steps(iters, prog, feed=feeds, fetch_list=[loss],
                          return_numpy=False)
    assert np.isfinite(np.asarray(lv)[-1])     # compile+warmup executed
    _median_window_throughput.last_warmup_s = time.perf_counter() - t0
    rates = []
    for _ in range(reps):
        t0 = time.perf_counter()
        (lv,) = exe.run_steps(iters, prog, feed=feeds, fetch_list=[loss],
                              return_numpy=False)
        assert np.isfinite(np.asarray(lv)[-1])   # barrier: window done
        rates.append(units_per_step * iters / (time.perf_counter() - t0))
    med = statistics.median(rates)
    return med, (max(rates) - min(rates)) / med


def _device_facts(require_tpu=True):
    """The device's facts for the printed line — or, when a chip is
    required and JAX found none, no run at all."""
    import jax
    dev = jax.devices()[0]
    if require_tpu and dev.platform != "tpu":
        sys.exit(f"bench.py: needs a TPU; JAX's default backend here is "
                 f"{dev.platform!r} ({dev.device_kind}).  Only "
                 f"`--mesh ... --simulate` runs without one.")
    return {"platform": dev.platform, "device_kind": dev.device_kind,
            "device_count": len(jax.devices())}


def main():
    device = _device_facts()
    import jax

    import paddle_tpu as pt
    from paddle_tpu import layers, models, profiler

    # runtime observability ON for the whole driver run: every timed
    # dispatch lands in the step-time histograms and the pipeline leg
    # records its queue/stall numbers — snapshotted into the JSON line
    # below (headline fields unchanged; host-side only, zero retraces).
    # When no metrics_log is already configured, the headline leg writes
    # a temp JSONL so the doctor budget + cost-model calibration ride
    # the committed line (a user-set PADDLE_TPU_METRICS_LOG is used
    # as-is, never clobbered).
    pt.flags.set_flag("observe", True)
    own_log = not pt.flags.get_flag("metrics_log")
    if own_log:
        import os
        import tempfile
        resnet_log = os.path.join(tempfile.gettempdir(),
                                  f"pt_bench_resnet_{os.getpid()}.jsonl")
        try:
            os.remove(resnet_log)
        except OSError:
            pass
        pt.flags.set_flag("metrics_log", resnet_log)
    else:
        resnet_log = None          # user-owned log: never doctored here

    img = layers.data("img", shape=[3, 224, 224], dtype="float32")
    label = layers.data("label", shape=[1], dtype="int64")
    pred = models.resnet50(img, num_classes=1000)
    loss = layers.mean(layers.cross_entropy(pred, label))
    opt = pt.optimizer.Momentum(learning_rate=0.01 / BATCH, momentum=0.9)
    opt.minimize(loss)

    # bf16 compute + fp32 master weights.  No conv here asks for the
    # Pallas 1x1 kernels (ops/pallas_conv.py, layers.conv2d(use_pallas=)):
    # they compile on the chip and match XLA (benchmark/conv_kernel.py
    # --steps 0) but have never been timed.  Route to them in the same
    # commit as an on-chip per-op A/B showing >=1.2x, together with the
    # re-measured driver number.
    exe = pt.Executor(amp=True)
    exe.run(pt.default_startup_program(), feed={}, fetch_list=[])

    rng = np.random.RandomState(0)
    # feeds live on device: a real input pipeline overlaps transfers
    feeds = {"img": jax.device_put(
        rng.rand(BATCH, 3, 224, 224).astype("float32")),
        "label": jax.device_put(rng.randint(0, 1000, (BATCH, 1)))}

    prog = pt.default_main_program()
    img_s, spread = _median_window_throughput(
        exe, prog, feeds, loss, units_per_step=BATCH, iters=80, reps=3)
    # snapshot NOW: the seq2seq/pipeline legs below reuse the timing core
    # and would overwrite last_warmup_s before the record is built
    resnet_warmup_s = getattr(_median_window_throughput, "last_warmup_s", 0.0)

    # doctor the headline leg from its own log window (before the other
    # legs write into it): measured budget + predicted-vs-measured
    # calibration row for the resnet program.  Only when the driver OWNS
    # a fresh temp log — a user-set PADDLE_TPU_METRICS_LOG appends
    # across runs, and a budget over earlier runs' events would attach a
    # wrong calibration ratio (run `paddle_tpu doctor` on such a log
    # directly instead).
    # (the calibration row is priced with this device_kind's row of
    # analysis.cost_model.DEVICE_PEAKS; an unknown kind raises)
    doctor_row = None
    if own_log:
        from paddle_tpu.observability import attribution
        report = attribution.doctor_report([resnet_log], program=prog,
                                           assume_batch=BATCH)
        doctor_row = {k: report.get(k)
                      for k in ("training", "calibration",
                                "top_bottleneck") if k in report}

    tok_s, tok_spread = _seq2seq_tokens_per_sec()
    pipe_row = _input_pipeline_speedup()

    line = {
        "metric": "resnet50_train_images_per_sec_per_chip",
        "value": round(img_s, 2),
        "unit": "img/s",
        "vs_baseline": round(img_s / BASELINE_IMG_S, 3),
        "window_spread": round(spread, 4),
        **device,
        # compile-time telemetry (core/compile_cache.py): how much of this
        # run went to trace/lower/compile, and how many XLA compiles JAX's
        # persistent cache served (jax_cache_hits)
        "compile_telemetry": {
            "first_dispatch_s": round(resnet_warmup_s, 3),
            "compile_phases_s": round(
                profiler.compile_stats().total_compile_seconds(), 3),
            "cache_counters": profiler.compile_stats().snapshot(),
        },
    }
    line["extra_metrics"] = [{
        "metric": "seq2seq_attn_train_tokens_per_sec_per_chip",
        "value": round(tok_s, 1),
        "unit": "tokens/s",
        "vs_baseline": None,   # reference unpublished (BASELINE.md)
        "window_spread": round(tok_spread, 4),
    }, {
        "metric": "input_pipeline_wide_deep_train_steps_per_sec",
        "value": pipe_row["pipelined_steps_per_s"],
        "unit": "steps/s",
        # vs the naive synchronous Trainer.train loop, same run
        "vs_baseline": pipe_row["speedup"],
        "window_spread": pipe_row["pipelined_spread"],
        # step-time budget + calibration from the extra doctored
        # pipelined pass (benchmark/input_pipeline.py _doctor_pass)
        "doctor": pipe_row.get("doctor"),
        "calibration": pipe_row.get("calibration"),
    }]
    if doctor_row is not None:
        line["doctor"] = doctor_row
    # full observability snapshot (step-time histograms, pipeline
    # queue-depth/stall numbers, compile counters, device memory where
    # the backend reports it) — BENCH_*.json gains these for free
    line["metrics_snapshot"] = profiler.metrics_snapshot()
    print(json.dumps(line))


def _input_pipeline_speedup():
    """End-to-end input-pipeline A/B on the wide_deep CTR ingestion
    workload (benchmark/input_pipeline.py): naive synchronous
    Trainer.train loop vs the pipelined run_pipelined path, median of
    paired alternating windows measured in THIS run."""
    import os
    import sys
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from benchmark.input_pipeline import WORKLOADS, run_workload

    WORKLOADS["wide_deep"]["full"]["reps"] = 4   # keep the driver fast
    return run_workload("wide_deep", quiet=True)  # ONE JSON line contract


def _seq2seq_tokens_per_sec(batch=64):
    """seq2seq+attention training tokens/s (benchmark/run.py seq2seq
    config; same pinned single-variant median-of-windows methodology as
    the headline metric)."""
    import jax

    import paddle_tpu as pt
    from paddle_tpu import layers, models

    pt.core.reset_default_programs()
    pt.core.reset_global_scope()
    pt.unique_name.reset()

    vocab, dim, src_len, tgt_len = 30000, 512, 30, 30
    src = layers.data("src", shape=[], dtype="int64", lod_level=1)
    tgt = layers.data("tgt", shape=[], dtype="int64", lod_level=1)
    lbl = layers.data("lbl", shape=[], dtype="int64", lod_level=1)
    probs = models.seq2seq_attention(src, tgt, vocab, vocab, emb_dim=dim,
                                     hidden_dim=dim)
    flat = layers.reshape(probs, [-1, vocab])
    loss = layers.mean(layers.cross_entropy(
        flat, layers.reshape(lbl, [-1, 1])))
    pt.optimizer.Adam(1e-3).minimize(loss)

    rng = np.random.RandomState(0)
    feeds = {"src": rng.randint(0, vocab, (batch, src_len)),
             "src@LEN": np.full(batch, src_len),
             "tgt": rng.randint(0, vocab, (batch, tgt_len)),
             "tgt@LEN": np.full(batch, tgt_len),
             "lbl": rng.randint(0, vocab, (batch, tgt_len)),
             "lbl@LEN": np.full(batch, tgt_len)}
    feeds = {k: jax.device_put(v) for k, v in feeds.items()}

    exe = pt.Executor(amp=True)
    exe.run(pt.default_startup_program(), feed={}, fetch_list=[])
    prog = pt.default_main_program()
    return _median_window_throughput(
        exe, prog, feeds, loss,
        units_per_step=batch * (src_len + tgt_len), iters=150, reps=5)


def _mesh_main(mesh_str: str, simulate: bool):
    """Planner-proposed-specs smoke on a (possibly simulated) mesh."""
    import os

    from paddle_tpu.cli import _parse_mesh

    axes = _parse_mesh(mesh_str)
    n_devices = 1
    for s in axes.values():
        n_devices *= s
    if simulate:
        # must land before the backend initializes; conftest-style live
        # config update below covers an already-imported jax.  An
        # existing (possibly smaller) device-count flag is REPLACED with
        # the max of both — keeping a stale value would fail the run
        # with advice to pass the flag that was already passed
        import re
        os.environ["JAX_PLATFORMS"] = "cpu"
        flags = os.environ.get("XLA_FLAGS", "")
        m = re.search(r"--xla_force_host_platform_device_count=(\d+)",
                      flags)
        count = max(n_devices, int(m.group(1)) if m else 0)
        flags = re.sub(r"--xla_force_host_platform_device_count=\d+",
                       "", flags)
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={count}"
        ).strip()
    device = _device_facts(require_tpu=not simulate)
    import jax

    import paddle_tpu as pt
    from paddle_tpu import layers
    from paddle_tpu.parallel import ShardedExecutor, mesh_for_axes

    mesh = mesh_for_axes(axes)

    # the smoke model: megatron-eligible widths (128-divisible) so a tp
    # axis actually exercises tensor splits, small enough for CPU
    batch = 64
    x = layers.data("x", shape=[256], dtype="float32")
    label = layers.data("label", shape=[1], dtype="int64")
    h = layers.fc(x, size=512, act="relu")
    pred = layers.fc(h, size=128, act="softmax")
    loss = layers.mean(layers.cross_entropy(pred, label))
    pt.optimizer.SGD(learning_rate=0.1).minimize(loss)
    prog = pt.default_main_program()

    rng = np.random.RandomState(0)
    feeds = {"x": rng.rand(batch, 256).astype("float32"),
             "label": rng.randint(0, 128, (batch, 1))}

    exe1 = pt.Executor()
    exe1.run(pt.default_startup_program(), feed={}, fetch_list=[])
    (ref,) = exe1.run(prog, feed=feeds, fetch_list=[loss])

    pt.core.reset_global_scope()
    exe = ShardedExecutor(mesh=mesh, batch_axis=next(iter(axes)),
                          auto_shard=True, validate=True)
    exe.run(pt.default_startup_program(), feed={}, fetch_list=[])
    exe._step = 0
    (sharded,) = exe.run(prog, feed=feeds, fetch_list=[loss])
    plan = exe.auto_plan
    rel_err = abs(float(sharded) - float(ref)) / max(1e-12, abs(float(ref)))

    # where the state and a batch really live: every device of the mesh
    # must hold a shard of a parameter, and a second step is fed a batch
    # already split over the mesh the way the executor splits host feeds —
    # not everything on device 0
    placed = {n: jax.device_put(v, jax.sharding.NamedSharding(
        mesh, exe._feed_spec(prog, n, v.ndim, v.shape)))
        for n, v in feeds.items()}
    (again,) = exe.run(prog, feed=placed, fetch_list=[loss])
    assert np.isfinite(again)
    param = prog.global_block().all_parameters()[0].name
    spread = {
        "param": param,
        "param_shard_devices": len({
            s.device.id for s in
            pt.global_scope().get(param).addressable_shards}),
        "feed_shard_devices": len({
            s.device.id for s in placed["x"].addressable_shards}),
        "feed_spec": str(placed["x"].sharding.spec),
    }
    spread_ok = (spread["param_shard_devices"] == n_devices
                 and spread["feed_shard_devices"] == n_devices)

    line = {
        "metric": "multichip_planner_smoke",
        "mesh": mesh_str,
        "n_devices": n_devices,
        **device,
        "simulated_cpu_mesh": simulate,
        "placement": spread,
        "plan_candidate": plan.candidate,
        "planner_param_specs": {
            k: [list(e) if e else None for e in v]
            for k, v in sorted(plan.param_specs.items())},
        "planner_feeds_sharded": len(plan.feed_specs),
        "per_device_peak_hbm_mb": round(
            plan.cost.peak_hbm_bytes_per_device / 1e6, 3),
        "step_time_proxy_ms": round(plan.cost.step_time_proxy_s * 1e3, 4),
        "sharded_vs_unsharded_rel_err": rel_err,
        "ok": bool(rel_err < 2e-4 and spread_ok),
        # this leg checks that a planned step runs and agrees; it times
        # nothing, on either platform
        "scaling_efficiency": None,
    }
    print(json.dumps(line))
    if not line["ok"]:
        sys.exit(1)


if __name__ == "__main__":
    # a leg that raises ends the run non-zero with its traceback: no
    # metric-shaped line is printed for a run that did not measure
    if "--mesh" in sys.argv:
        _mesh_main(sys.argv[sys.argv.index("--mesh") + 1],
                   simulate="--simulate" in sys.argv)
    else:
        main()

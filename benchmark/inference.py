"""Inference benchmark on the AOT StableHLO deploy path (VERDICT r4
Missing #5): the reference PUBLISHED inference throughput for ResNet-50
bs1/4/16 (benchmark/IntelOptimizedPaddle.md:81-85 — 107.8 / 182.7 / 217.7
img/s on 2x Skylake 6148); this measures the same metric for the exported
artifact (export_model.py) on the real chip, plus the seq2seq beam
decoder, and writes benchmark/inference_results.json.

Methodology: the artifact is loaded fresh via ``load_compiled_model`` (the
deploy-ABI binding — parameters baked in, no Program/Scope), then M calls
are dispatched back-to-back and only the LAST output is fetched; devices
queue async dispatches, so total/M approximates device step time with the
host round trip paid once (measured separately as
``latency_roundtrip_s``).  Single-call round-trip latency is reported
alongside — that is what an on-host server without pipelining would see.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import paddle_tpu as pt  # noqa: E402
from paddle_tpu import layers, models  # noqa: E402

# artifact-loading/feed-synthesis shared with benchmark/serving.py — the
# deploy-ABI benchmark and the serving benchmark measure ONE model/
# manifest path (ISSUE 8 satellite: no drift between the two)
from benchmark.serving_common import (closed_loop,  # noqa: E402
                                      feeds_from_manifest, load_artifact,
                                      percentile, single_example)

OUT = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                   "inference_results.json")


def _force(x):
    return np.asarray(x[0]).ravel()[:1]


def _time_pipelined(run, feeds, out_count_per_call, windows=5, target_s=2.0):
    import jax
    feeds = jax.device_put(feeds)       # stage once; calls then enqueue
    out = run(feeds)
    _force(out)
    t0 = time.perf_counter()
    _force(run(feeds))
    per_call_rt = time.perf_counter() - t0          # incl. host round trip
    M = max(10, int(target_s / max(per_call_rt, 1e-4)))
    times = []
    for _ in range(windows):
        t0 = time.perf_counter()
        for _ in range(M - 1):
            out = run(feeds)
        out = run(feeds)
        _force(out)
        times.append((time.perf_counter() - t0) / M)
    med = float(np.median(times))
    return {"per_call_s": med,
            "throughput_per_s": out_count_per_call / med,
            "latency_roundtrip_s": per_call_rt, "calls_per_window": M,
            "spread_pct": 100.0 * (max(times) - min(times)) / med}


def _time_device_scan(run, feeds, out_count_per_call, est_call_s,
                      windows=5):
    """True device step time: K chained calls inside ONE jit dispatch (a
    lax.scan whose carry is a data-dependent ~0 perturbation of the feed,
    so XLA cannot hoist or elide iterations) — the inference analog of the
    training benches' run_steps methodology.  Removes per-call host
    dispatch from the measurement."""
    import functools

    import jax
    import jax.numpy as jnp
    from jax import lax

    feeds = jax.device_put(feeds)
    name = next(n for n, v in feeds.items())
    float_feed = jnp.issubdtype(feeds[name].dtype, jnp.floating)

    @functools.partial(jax.jit, static_argnames=("k",))
    def runk(feeds, k):
        def body(c, _):
            f = dict(feeds)
            f[name] = f[name] + c.astype(f[name].dtype)
            outs = run(f)
            dep = next(o for o in outs
                       if jnp.issubdtype(o.dtype, jnp.floating))
            d = dep.ravel()[0] * 1e-30      # data-dependent, ~0 numerically
            return (d if float_feed else d.astype(jnp.int64)), None
        c, _ = lax.scan(body, jnp.zeros((), jnp.float32)
                        if float_feed else jnp.zeros((), jnp.int64),
                        None, length=k)
        return c

    warmed = set()

    def window(k, n=1):
        if k not in warmed:                 # compile/warm once per k
            _force([runk(feeds, k)])
            warmed.add(k)
        ts = []
        for _ in range(n):
            t0 = time.perf_counter()
            _force([runk(feeds, k)])
            ts.append(time.perf_counter() - t0)
        return float(np.median(ts))

    lat = window(1, n=3)                    # round-trip floor
    # adaptive k: the device step can be under the host round trip (bs1
    # ResNet fwd is sub-millisecond), so probe and scale until the scan
    # body dominates the window
    k = int(np.clip(1.5 / max(est_call_s, 1e-3), 64, 512))
    probe = window(k)
    est = max((probe - lat) / k, 2e-7)
    k = int(np.clip(1.0 / est, k, 20000))
    times = [window(k) for _ in range(windows)]
    med = float(np.median(times))
    eff = max((med - lat) / k, 1e-9)
    return {"device_step_s": eff,
            "device_throughput_per_s": out_count_per_call / eff,
            "k": k, "latency_floor_s": lat,
            "device_spread_pct": 100.0 * (max(times) - min(times)) / med}


def export_resnet50(tmpdir="/tmp/pt_infer_resnet"):
    """Export the ResNet-50 inference artifact (shared by the throughput
    benches below and by ``--server`` mode)."""
    pt.core.reset_default_programs()
    pt.core.reset_global_scope()
    pt.unique_name.reset()
    img = layers.data("img", shape=[3, 224, 224], dtype="float32")
    pred = models.resnet50(img, num_classes=1000)
    exe = pt.Executor()
    exe.run(pt.default_startup_program(), feed={}, fetch_list=[])
    pt.export_compiled_model(tmpdir, {"img": ((-1, 3, 224, 224), "float32")},
                             [pred])
    pt.core.reset_default_programs()
    pt.core.reset_global_scope()
    return tmpdir


def bench_resnet50(batches=(1, 4, 16, 64, 128), tmpdir="/tmp/pt_infer_resnet"):
    run, manifest = load_artifact(export_resnet50(tmpdir))
    rows = {}
    rng = np.random.RandomState(0)
    for b in batches:
        feeds = feeds_from_manifest(manifest, b, rng)
        r = _time_pipelined(run, feeds, out_count_per_call=b)
        r.update(_time_device_scan(run, feeds, out_count_per_call=b,
                                   est_call_s=r["per_call_s"]))
        rows[f"bs{b}"] = r
        print(json.dumps({"resnet50_infer": f"bs{b}", **r}), flush=True)
    return rows


def bench_seq2seq_decode(batches=(1, 16, 64), tmpdir="/tmp/pt_infer_s2s"):
    """Beam-4 decoding, src len 30, max 30 generated tokens, d512,
    vocab 30k — the training benchmark's config on the generation path."""
    pt.core.reset_default_programs()
    pt.core.reset_global_scope()
    pt.unique_name.reset()
    V, T = 30000, 30
    src = layers.data("src", shape=[T], dtype="int64")
    ids, scores, lens = models.seq2seq_infer(
        src, src_vocab_size=V, tgt_vocab_size=V, emb_dim=512,
        hidden_dim=512, beam_size=4, max_len=T)
    exe = pt.Executor()
    exe.run(pt.default_startup_program(), feed={}, fetch_list=[])
    pt.export_compiled_model(tmpdir, {"src": ((-1, T), "int64")},
                             [ids, scores, lens])
    pt.core.reset_default_programs()
    pt.core.reset_global_scope()
    run, _ = pt.load_compiled_model(tmpdir)
    rows = {}
    rng = np.random.RandomState(0)
    for b in batches:
        feeds = {"src": rng.randint(2, V, (b, T)).astype("int64")}
        # tokens/s accounting: B x max_len best-hypothesis tokens out.
        # No device-scan variant here: a beam decode call is tens of ms,
        # far above the dispatch floor, and each extra scan length costs
        # another multi-minute decoder compile
        r = _time_pipelined(run, feeds, out_count_per_call=b * T)
        rows[f"bs{b}"] = r
        print(json.dumps({"seq2seq_beam4_decode": f"bs{b}", **r}),
              flush=True)
    return rows


def bench_server(tmpdir="/tmp/pt_infer_resnet", duration_s=4.0,
                 workers=32, max_batch=16, max_wait_ms=5.0,
                 model_name="resnet50"):
    """``--server`` mode: drive the SAME exported artifact through the
    serving runtime (paddle_tpu.serving.Server) instead of raw
    ``load_compiled_model`` calls — the deploy-ABI benchmark and the
    serving benchmark share one model/manifest path, and this row is the
    server-mediated counterpart of the raw per-call rows above (the
    delta is the batching/admission layer's cost and win)."""
    from paddle_tpu.serving import Model, Server
    from paddle_tpu.serving.server import _buckets

    if not os.path.exists(os.path.join(tmpdir, "manifest.json")):
        if model_name != "resnet50":
            raise SystemExit(f"--artifact {tmpdir!r}: no manifest.json")
        export_resnet50(tmpdir)
    _, manifest = load_artifact(tmpdir)
    rng = np.random.RandomState(0)
    example = single_example(manifest, rng)

    # warm EVERY bucket (same fidelity rule as benchmark/serving.py's
    # _make_server): a mid-window compile would smear seconds of one-off
    # cost into the p50/p99 this row is compared on
    srv = Server(max_batch=max_batch, max_wait_ms=max_wait_ms,
                 deadline_ms=None, queue_capacity=max(256, 4 * workers),
                 warmup_buckets=_buckets(max_batch))
    srv.add_model(Model.from_artifact(tmpdir, name=model_name))
    srv.start()
    try:
        lat, loop_row = closed_loop(srv, example, workers=workers,
                                    duration_s=duration_s)
        health = srv.health()["models"][model_name]
    finally:
        srv.shutdown(drain=True)
    lat_ms = [v * 1e3 for v in lat]
    row = {
        "model": model_name, "artifact": tmpdir,
        "max_batch": max_batch, "max_wait_ms": max_wait_ms,
        **loop_row,
        "latency_ms_p50": round(percentile(lat_ms, 0.50), 2)
        if lat_ms else None,
        "latency_ms_p99": round(percentile(lat_ms, 0.99), 2)
        if lat_ms else None,
        "batches": health["batches"],
        "mean_batch": round(health["served"] / health["batches"], 2)
        if health["batches"] else None,
    }
    print(json.dumps({"server": row}), flush=True)
    return row


def main(argv=None):
    ap = argparse.ArgumentParser(description="deploy-ABI inference "
                                 "benchmark (see module docstring)")
    ap.add_argument("which", nargs="*", default=["resnet50", "seq2seq"],
                    help="benches to run (resnet50, seq2seq)")
    ap.add_argument("--server", action="store_true",
                    help="drive the exported artifact through the "
                         "serving runtime (paddle_tpu serve engine) "
                         "instead of raw artifact calls")
    ap.add_argument("--duration-s", type=float, default=4.0)
    ap.add_argument("--workers", type=int, default=32)
    ap.add_argument("--max-batch", type=int, default=16)
    ap.add_argument("--artifact", default=None,
                    help="serve this exported dir instead of the "
                         "resnet50 artifact (--server mode only)")
    args = ap.parse_args(argv)

    import jax
    results = {"device": str(jax.devices()[0])}
    if os.path.exists(OUT):                 # merge partial runs (keeps
        with open(OUT) as f:                # the committed rows' device
            results.update(json.load(f))    # provenance intact)
    if args.server:
        kw = {}
        if args.artifact:
            kw = {"tmpdir": args.artifact,
                  "model_name": os.path.basename(
                      os.path.normpath(args.artifact))}
        results["server"] = {
            "device": str(jax.devices()[0]),
            **bench_server(duration_s=args.duration_s,
                           workers=args.workers,
                           max_batch=args.max_batch, **kw)}
    else:
        if "resnet50" in args.which:
            results["resnet50"] = bench_resnet50()
        if "seq2seq" in args.which:
            results["seq2seq_beam4"] = bench_seq2seq_decode()
    with open(OUT, "w") as f:
        json.dump(results, f, indent=1)
    print(f"wrote {OUT}")


if __name__ == "__main__":
    main()

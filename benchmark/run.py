#!/usr/bin/env python
"""Benchmark runner mirroring the reference's benchmark/paddle suite
(benchmark/paddle/image/run.sh configs + benchmark/paddle/rnn/run.sh), plus
the seq2seq tokens/s metric BASELINE.json asks for.

Usage:
    python benchmark/run.py --model resnet50 --batch 64 --amp
    python benchmark/run.py --all            # every headline config

Prints one JSON line per config:
    {"model", "batch", "ms_per_batch", "throughput", "unit", "ref", "speedup"}
``ref`` is the reference's published number for that config (BASELINE.md),
converted to the same unit; null when the reference published none.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


# reference numbers (BASELINE.md): config -> (ms/batch, source)
REF_MS = {
    ("alexnet", 64): 195.0, ("alexnet", 128): 334.0,
    ("alexnet", 256): 602.0, ("alexnet", 512): 1629.0,
    ("googlenet", 64): 613.0, ("googlenet", 128): 1149.0,
    ("googlenet", 256): 2348.0,
    ("smallnet", 64): 10.463,
    ("lstm_h256", 64): 83.0, ("lstm_h512", 64): 184.0,
    ("lstm_h1280", 64): 641.0, ("lstm_h512", 128): 261.0,
    ("lstm_h512", 256): 414.0,
}
# img/s references (CPU MKL-DNN table, best published for these models)
REF_IMG_S = {("resnet50", 64): 81.69, ("resnet50", 128): 82.35,
             ("vgg19", 64): 28.46, ("vgg19", 128): 29.83}


def _build_image(model, batch):
    import paddle_tpu as pt
    from paddle_tpu import layers, models
    size = {"alexnet": 224, "googlenet": 224, "resnet50": 224,
            "vgg19": 224, "smallnet": 32}[model]
    img = layers.data("img", shape=[3, size, size], dtype="float32")
    label = layers.data("label", shape=[1], dtype="int64")
    num_classes = 10 if model == "smallnet" else 1000
    if model == "alexnet":
        pred = models.alexnet(img, num_classes)
    elif model == "googlenet":
        pred = models.googlenet(img, num_classes)
    elif model == "resnet50":
        pred = models.resnet50(img, num_classes)
    elif model == "vgg19":
        pred = models.vgg19(img, num_classes)
    else:
        pred = models.vgg_cifar(img, num_classes)
    loss = layers.mean(layers.cross_entropy(pred, label))
    pt.optimizer.Momentum(learning_rate=0.01 / batch, momentum=0.9) \
        .minimize(loss)
    rng = np.random.RandomState(0)
    feeds = {"img": rng.rand(batch, 3, size, size).astype("float32"),
             "label": rng.randint(0, num_classes, (batch, 1))}
    return loss, feeds, batch


def _build_lstm(hidden, batch, seq_len=100, vocab=30000, emb=128,
                lstm_num=2):
    """benchmark/paddle/rnn/rnn.py: emb -> N stacked LSTM -> last -> fc2."""
    import paddle_tpu as pt
    from paddle_tpu import layers, models
    words = layers.data("words", shape=[], dtype="int64", lod_level=1)
    label = layers.data("label", shape=[1], dtype="int64")
    pred = models.lstm_text_classification(
        words, vocab_size=vocab, num_classes=2, emb_dim=emb,
        hidden_size=hidden, lstm_num=lstm_num)
    loss = layers.mean(layers.cross_entropy(pred, label))
    pt.optimizer.Adam(2e-3).minimize(loss)
    rng = np.random.RandomState(0)
    feeds = {"words": rng.randint(0, vocab, (batch, seq_len)),
             "words@LEN": np.full(batch, seq_len),
             "label": rng.randint(0, 2, (batch, 1))}
    return loss, feeds, batch


def _build_seq2seq(batch, src_len=30, tgt_len=30, vocab=30000, dim=512,
                   lazy_adam=False):
    import paddle_tpu as pt
    from paddle_tpu import layers, models
    src = layers.data("src", shape=[], dtype="int64", lod_level=1)
    tgt = layers.data("tgt", shape=[], dtype="int64", lod_level=1)
    lbl = layers.data("lbl", shape=[], dtype="int64", lod_level=1)
    probs = models.seq2seq_attention(src, tgt, vocab, vocab, emb_dim=dim,
                                     hidden_dim=dim)
    flat = layers.reshape(probs, [-1, vocab])
    loss = layers.mean(layers.cross_entropy(
        flat, layers.reshape(lbl, [-1, 1])))
    pt.optimizer.Adam(1e-3, lazy_mode=lazy_adam).minimize(loss)
    rng = np.random.RandomState(0)
    feeds = {"src": rng.randint(0, vocab, (batch, src_len)),
             "src@LEN": np.full(batch, src_len),
             "tgt": rng.randint(0, vocab, (batch, tgt_len)),
             "tgt@LEN": np.full(batch, tgt_len),
             "lbl": rng.randint(0, vocab, (batch, tgt_len)),
             "lbl@LEN": np.full(batch, tgt_len)}
    # tokens processed per batch = batch * (src + tgt)
    return loss, feeds, batch * (src_len + tgt_len)


def run_config(name, batch, amp=True, iters=None, reps=3):
    import statistics

    import jax
    import paddle_tpu as pt

    pt.core.reset_default_programs()
    pt.core.reset_global_scope()
    pt.unique_name.reset()

    if name.startswith("lstm_h"):
        loss, feeds, units = _build_lstm(int(name[6:]), batch)
        unit = "samples/s"
    elif name == "seq2seq":
        loss, feeds, units = _build_seq2seq(batch)
        unit = "tokens/s"
    else:
        loss, feeds, units = _build_image(name, batch)
        unit = "img/s"

    exe = pt.Executor(amp=amp)
    exe.run(pt.default_startup_program(), feed={}, fetch_list=[])
    feeds = {k: jax.device_put(v) for k, v in feeds.items()}
    prog = pt.default_main_program()
    # Pinned methodology (round 4, see RESULTS.md): each window is ONE
    # compiled dispatch of `iters` steps (Executor.run_steps — device-side
    # lax.scan with donated state), so the per-step host dispatch rate is
    # out of the measurement; first call = compile + warmup.
    # Fixed window sizes (no probe compiles): big CNNs 60 steps, small
    # models 300.
    if iters is None:
        iters = 60 if name in ("alexnet", "googlenet", "resnet50",
                               "vgg19") else 300
    (lv,) = exe.run_steps(iters, prog, feed=feeds, fetch_list=[loss],
                          return_numpy=False)
    assert np.isfinite(np.asarray(lv)[-1])
    rates = []
    for _ in range(reps):
        t0 = time.perf_counter()
        (lv,) = exe.run_steps(iters, prog, feed=feeds, fetch_list=[loss],
                              return_numpy=False)
        assert np.isfinite(np.asarray(lv)[-1])
        rates.append(units * iters / (time.perf_counter() - t0))
    thr = statistics.median(rates)
    spread = (max(rates) - min(rates)) / thr
    dt = units / thr
    ref_ms = REF_MS.get((name, batch))
    ref_thr = REF_IMG_S.get((name, batch))
    if ref_thr is None and ref_ms is not None:
        ref_thr = units / (ref_ms / 1e3)
    out = {"model": name, "batch": batch,
           "ms_per_batch": round(dt * 1e3, 2),
           "throughput": round(thr, 1), "unit": unit,
           "ref": ref_thr, "amp": amp,
           "speedup": round(thr / ref_thr, 2) if ref_thr else None,
           "window_spread": round(spread, 4)}
    print(json.dumps(out), flush=True)
    return out


HEADLINE = [("alexnet", 128), ("googlenet", 128), ("smallnet", 64),
            ("resnet50", 64), ("vgg19", 64),
            ("lstm_h512", 64), ("lstm_h512", 128), ("seq2seq", 64)]


def run_input_pipeline(smoke=False):
    """Delegate to benchmark/input_pipeline.py (naive vs pipelined
    Trainer.train A/B); one JSON line per workload, same as run_config."""
    from benchmark.input_pipeline import WORKLOADS, run_workload
    return [run_workload(w, smoke=smoke) for w in sorted(WORKLOADS)]


def run_autotune(smoke=False):
    """Delegate to benchmark/autotune.py (tuned-vs-default A/B per
    host-side tunable through the real search path); one JSON summary
    line per tunable, same shape as the committed rows."""
    import tempfile

    from benchmark.autotune import HOST_TUNABLES, run_one
    with tempfile.TemporaryDirectory(prefix="pt-autotune-") as store:
        return [run_one(n, store, smoke=smoke)
                for n in sorted(HOST_TUNABLES)]


def run_ctr(smoke=False):
    """Delegate to benchmark/ctr.py (host-resident sparse parameter
    server vs dense-embedding control, lookup latency, push throughput,
    zipfian cache hit rate, doctor budget)."""
    from benchmark.ctr import run_all
    return [run_all(smoke=smoke)]


def run_decode(smoke=False):
    """Delegate to benchmark/decode.py (continuous-batching KV-cache
    decode slot pool vs static-batch control: decode tokens/s paired
    A/B, TTFT/inter-token percentiles, slot occupancy, doctor budget)."""
    from benchmark.decode import run_all
    return [run_all(smoke=smoke)]


def run_pserver(smoke=False):
    """Delegate to benchmark/pserver.py (multi-host sparse parameter
    server: batched binary wire vs naive JSON A/B, remote pull latency
    vs in-process, shard pipelining A/B over a real process fleet)."""
    from benchmark.pserver import run_all
    return [run_all(smoke=smoke)]


def run_checkpoint(smoke=False):
    """Delegate to benchmark/checkpoint.py (incremental checkpointing:
    delta-commit vs full-save wall/bytes A/B, elastic task-boundary
    commit throughput, base+K-delta chain restore cost)."""
    from benchmark.checkpoint import run_all
    return [run_all(smoke=smoke)]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default=None,
                    help="model config, 'input_pipeline' for the "
                         "naive-vs-pipelined input A/B, 'autotune' "
                         "for the tuned-vs-default autotuner A/B, "
                         "'ctr' for the sparse-parameter-server CTR A/B, "
                         "'decode' for the continuous-batching "
                         "incremental-decode A/B, 'pserver' for the "
                         "multi-host sparse parameter-server wire A/B, "
                         "or 'checkpoint' for the incremental-"
                         "checkpoint delta-vs-full A/B")
    ap.add_argument("--smoke", action="store_true",
                    help="input_pipeline/autotune/ctr/"
                         "decode/pserver/checkpoint only: seconds-fast "
                         "path check")
    ap.add_argument("--batch", type=int, default=64)
    ap.add_argument("--iters", type=int, default=None,
                    help="steps per timed window (default: 60 for the "
                         "big CNNs, 300 otherwise)")
    ap.add_argument("--amp", action="store_true", default=True)
    ap.add_argument("--no-amp", dest="amp", action="store_false")
    ap.add_argument("--all", action="store_true")
    args = ap.parse_args()
    if args.model == "input_pipeline":
        run_input_pipeline(smoke=args.smoke)
        return
    if args.model == "autotune":
        run_autotune(smoke=args.smoke)
        return
    if args.model == "ctr":
        run_ctr(smoke=args.smoke)
        return
    if args.model == "decode":
        run_decode(smoke=args.smoke)
        return
    if args.model == "pserver":
        run_pserver(smoke=args.smoke)
        return
    if args.model == "checkpoint":
        run_checkpoint(smoke=args.smoke)
        return
    if args.all:
        for name, batch in HEADLINE:
            try:
                run_config(name, batch, amp=args.amp, iters=args.iters)
            except Exception as e:
                print(json.dumps({"model": name, "batch": batch,
                                  "error": str(e)[:200]}), flush=True)
    else:
        run_config(args.model, args.batch, amp=args.amp, iters=args.iters)


if __name__ == "__main__":
    main()

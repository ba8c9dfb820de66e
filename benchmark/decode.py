#!/usr/bin/env python
"""Continuous-batching incremental decode benchmark: the KV-cache slot
pool (paddle_tpu.serving.decode) vs a static-batch control (ROADMAP
item 5; the serving analog of the Orca iteration-level scheduler).

Both arms run the SAME two compiled step functions (batch-1 prefill +
batch-S one-token decode over donated cache slabs) on the SAME
mixed-length request trace; the only difference is the scheduler:

* ``continuous`` — at every token-step boundary, finished sequences
  (max-len here; EOS in general) are evicted and completed immediately
  and queued requests are admitted into the freed slots;
* ``static`` — requests are admitted only into an EMPTY pool, and the
  whole batch then runs until its slowest member finishes (pad to the
  longest: the classic request-batcher behavior a generate workload
  degrades to).

Measured rows, all REAL and in-container (CPU; the TPU row is a
pending-hardware stub per the PR 1 convention):

* ``decode tokens/s`` — generated-token throughput per arm;
* ``ttft_ms`` — p50/p99 time to first token (admission -> prefill);
* ``inter_token_ms`` — p50/p99 gap between consecutive tokens of one
  sequence (the streaming cadence continuous batching bounds);
* ``slot_occupancy`` — live slots over total at decode steps (the
  padded-compute complement);
* ``ab`` — paired alternating static-vs-continuous A/B per the PR 9
  discipline (median of per-pair ratios, noise gate, raw windows
  committed), acceptance bar 1.3x decode tokens/s;
* ``arms_tokens_identical`` — every request's generated tokens must be
  BIT-identical across the two schedulers (per-row bit independence of
  ``attention_with_cache`` + the recompute oracle in
  tests/test_decode.py make scheduling invisible to the math);
* ``doctor`` — the decode section of the PR 10 measured-vs-modeled
  budget, attached from one extra observed window.

Writes benchmark/decode_results.json.

Usage::

    python benchmark/decode.py [--smoke] [--out PATH]
    python benchmark/run.py --model decode [--smoke]
"""
from __future__ import annotations

import argparse
import itertools
import json
import os
import sys
import time

import numpy as np

_DOCTOR_SEQ = itertools.count()

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

OUT = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                   "decode_results.json")

FULL = {
    "vocab": 256,
    "hidden": 64,
    "n_layers": 2,
    "slots": 8,
    "max_len": 64,
    "n_requests": 24,
    "prompt_lens": (4, 6, 8, 12),
    "max_news": (4, 8, 8, 48),      # long-tail mix: the static arm pads
                                    # every round to its slowest member,
                                    # continuous streams the short ones
                                    # through the freed slots
    "ab_pairs": 5,
    "warmup": 1,
    "min_speedup": 1.3,
}
SMOKE = {
    "vocab": 64,
    "hidden": 32,
    "n_layers": 1,
    "slots": 4,
    "max_len": 32,
    "n_requests": 6,
    "prompt_lens": (3, 5, 7),
    "max_news": (2, 4, 8),
    "ab_pairs": 2,
    "warmup": 1,
    "min_speedup": 1.3,
}


def _trace(cfg, seed=0):
    """The shared mixed-length request trace: (prompt, max_new) pairs.
    eos_id is None, so every request generates exactly max_new tokens —
    deterministic work per window by construction."""
    rng = np.random.RandomState(seed)
    out = []
    for i in range(cfg["n_requests"]):
        plen = cfg["prompt_lens"][i % len(cfg["prompt_lens"])]
        prompt = [int(t) for t in rng.randint(1, cfg["vocab"], plen)]
        out.append((prompt, cfg["max_news"][i % len(cfg["max_news"])]))
    return out


def _build_pool(cfg, mode):
    from paddle_tpu.serving.decode import DecodeEngine, DecodeRuntime

    eng = DecodeEngine(
        vocab_size=cfg["vocab"], hidden_dim=cfg["hidden"],
        n_layers=cfg["n_layers"], slots=cfg["slots"],
        max_len=cfg["max_len"], eos_id=None, seed=7,
        name=f"bench-{mode}")
    rt = DecodeRuntime(eng, mode=mode, step_wait_ms=0.5,
                       default_deadline_ms=None)
    rt.start(warmup=True)
    return rt


def _run_window(rt, trace):
    """Submit the whole trace (closed queue of offered load), wait for
    every completion; returns (wall_s, outputs)."""
    t0 = time.perf_counter()
    reqs = [rt.submit(p, m) for p, m in trace]
    outs = [r.result(timeout=600.0) for r in reqs]
    return time.perf_counter() - t0, outs


def _arm_row(rt, trace, outs, wall_s, h0, h1):
    tokens = sum(len(o["tokens"]) for o in outs)
    ttfts = sorted(o["ttft_ms"] for o in outs if o["ttft_ms"] is not None)
    inter = sorted(g for o in outs for g in o["inter_token_ms"])
    steps = h1["steps"] - h0["steps"]
    # decode-step tokens = all generated minus the prefill-emitted firsts
    step_tokens = (h1["tokens"] - h0["tokens"]) - len(outs)

    def pctl(xs, q):
        return round(float(np.percentile(np.asarray(xs, np.float64), q)),
                     3) if xs else None

    return {
        "mode": rt.mode,
        "tokens": tokens,
        "decode_tokens_per_s": round(tokens / wall_s, 1),
        "wall_s": round(wall_s, 3),
        "ttft_ms": {"p50": pctl(ttfts, 50), "p99": pctl(ttfts, 99)},
        "inter_token_ms": {"p50": pctl(inter, 50), "p99": pctl(inter, 99)},
        "decode_steps": steps,
        "slot_occupancy": round(step_tokens / (steps * rt.engine.slots), 4)
        if steps else None,
    }


def run_ab(cfg, quiet=False):
    """The headline A/B: one persistent pool per mode (engines compiled
    once, outside every timed window), alternating windows over the same
    trace, PR 9 paired discipline at the 1.3x bar."""
    from paddle_tpu.tuning.search import paired_ab

    trace = _trace(cfg)
    pools = {m: _build_pool(cfg, m) for m in ("static", "continuous")}
    try:
        last_outs = {}

        def measure(config):
            rt = pools[config["mode"]]
            _, outs = _run_window(rt, trace)
            last_outs[config["mode"]] = outs

        ab = paired_ab(measure, {"mode": "static"},
                       {"mode": "continuous"}, pairs=cfg["ab_pairs"],
                       warmup=cfg["warmup"],
                       min_speedup=cfg["min_speedup"])

        # per-arm detail rows from one more (untimed-by-the-AB) window
        rows = {}
        for mode, rt in pools.items():
            h0 = rt.health()
            wall, outs = _run_window(rt, trace)
            rows[mode] = _arm_row(rt, trace, outs, wall, h0, rt.health())
            last_outs[mode] = outs

        # the integrity bar: scheduling must be invisible to the math —
        # every request's token ids bitwise equal across schedulers
        identical = all(
            a["tokens"] == b["tokens"]
            for a, b in zip(last_outs["static"], last_outs["continuous"]))
    finally:
        for rt in pools.values():
            rt.shutdown(drain=True, timeout=60.0)
    row = {"ab": ab, "static": rows["static"],
           "continuous": rows["continuous"],
           "arms_tokens_identical": bool(identical)}
    if not quiet:
        print(json.dumps({
            "arm": "decode_ab", "speedup": ab["speedup"],
            "accepted": ab["accepted"],
            "static_tokens_per_s": rows["static"]["decode_tokens_per_s"],
            "continuous_tokens_per_s":
                rows["continuous"]["decode_tokens_per_s"],
            "arms_tokens_identical": bool(identical)}), flush=True)
    return row


def run_doctor_pass(cfg, quiet=False):
    """One extra OBSERVED continuous window (instrumentation never
    touches the A/B): the decode section of the stats summary + the
    doctor's token-step budget ride a JSONL log."""
    import tempfile

    from paddle_tpu import flags
    from paddle_tpu.observability import attribution
    from paddle_tpu.observability.export import summarize_logs

    # unique path per pass: the JSONL writer keeps a same-path handle
    # open across calls, so a removed-and-reused name would stream to an
    # unlinked inode
    log = os.path.join(
        tempfile.gettempdir(),
        f"pt_doctor_decode_{os.getpid()}_{next(_DOCTOR_SEQ)}.jsonl")
    try:
        os.remove(log)
    except OSError:
        pass
    rt = _build_pool(cfg, "continuous")
    prev_obs = flags.get_flag("observe")
    prev_log = flags.get_flag("metrics_log")
    flags.set_flag("observe", True)
    flags.set_flag("metrics_log", log)
    try:
        _run_window(rt, _trace(cfg))
    finally:
        flags.set_flag("observe", prev_obs)
        flags.set_flag("metrics_log", prev_log or "")
        rt.shutdown(drain=True, timeout=60.0)
    summary = summarize_logs([log])
    report = attribution.doctor_report([log])
    row = {"doctor": report.get("decode"),
           "stats_decode": summary.get("decode")}
    if not quiet:
        print(json.dumps({"arm": "doctor", **row}), flush=True)
    return row


def run_all(cfg=None, smoke=False, quiet=False):
    cfg = cfg or (SMOKE if smoke else FULL)
    row = run_ab(cfg, quiet=quiet)
    try:
        doctor_row = run_doctor_pass(cfg, quiet=quiet)
    except Exception as e:   # A/B rows must survive a doctor failure
        doctor_row = {"doctor": {"error": f"{type(e).__name__}: {e}"}}
    return {"config": dict(cfg), **row, **doctor_row,
            "smoke": bool(smoke)}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="seconds-fast path check (tiny sizes); does "
                         "not overwrite the committed results file")
    ap.add_argument("--out", default=OUT)
    args = ap.parse_args()

    row = run_all(smoke=args.smoke)
    print(json.dumps(row, indent=1))
    if args.smoke:
        return
    result = {
        "benchmark": "decode_continuous_batching",
        "device": "cpu (in-container; no TPU reachable)",
        "cpu": row,
        "tpu": {
            "status": "pending-hardware",
            "plan": "re-run benchmark/decode.py on a chip host: the "
                    "decode step is the same compiled one-token program "
                    "(donated [S, Tmax, D] cache slabs in HBM); on-chip "
                    "the per-step dispatch shrinks and the padded-"
                    "compute fraction static batching wastes grows with "
                    "the matmul width, so the continuous win should "
                    "widen — commit real rows, never extrapolate these",
            "rows": [],
        },
    }
    with open(args.out, "w") as fh:
        json.dump(result, fh, indent=1)
    print(f"wrote {args.out}")


if __name__ == "__main__":
    main()

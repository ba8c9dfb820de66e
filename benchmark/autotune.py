#!/usr/bin/env python
"""Autotuner A/B: tuned-vs-default on the host-side tunables, committed.

For each host-side tunable with a built-in target
(``paddle_tpu.tuning.targets``) this driver runs the REAL search path —
``tuning.search.tune``: grid over the declared space, then the paired
alternating default-vs-winner A/B whose headline is the MEDIAN OF
PER-PAIR RATIOS (the PR 2 discipline; this container's throughput drifts
2-3x on multi-minute timescales and pairing cancels what independent
medians cannot) — and commits the outcome VERBATIM: a winner only when
the noise gate accepts it, otherwise the gate's explicit refusal WITH
the raw windows.  Either is a valid committed row; a fabricated speedup
is not.

Winners are persisted to a store directory (default: a throwaway tmp
dir; pass ``--cache-dir`` to keep them for replay via
``PADDLE_TPU_AUTOTUNE=1``), proving the full search → persist → replay
loop in one run.

Usage:
    python benchmark/autotune.py              # full A/B, writes
                                              # autotune_results.json
    python benchmark/autotune.py --smoke      # seconds-fast path check
    python benchmark/autotune.py --target serving/batcher
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

RESULTS_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "autotune_results.json")

HOST_TUNABLES = ("executor/run_pipelined", "serving/batcher",
                 "reader/prefetch")


def run_one(name: str, store_dir: str, smoke: bool, quiet: bool = False):
    from paddle_tpu.tuning import search, targets

    targets.ensure_registered(name)
    measure = targets.build_target(name, smoke=smoke)

    def on_trial(t):
        if not quiet:
            print(json.dumps({"tunable": name, "trial": t.config,
                              "status": t.status, "seconds": t.seconds}),
                  flush=True)

    doc = search.tune(name, measure,
                      reps=2 if smoke else 3,
                      pairs=3 if smoke else 7,
                      budget=4 if smoke else None,
                      base=store_dir, save=True, on_trial=on_trial)
    trials = doc.get("search", {}).get("trials", [])
    row = {
        "tunable": name,
        "status": doc["status"],
        "default": doc.get("search", {}).get("default"),
        "winner": doc.get("winner"),
        "trials": [{"config": t["config"], "status": t["status"],
                    "seconds": t["seconds"]} for t in trials],
        "smoke": smoke,
    }
    ab = doc.get("ab")
    if ab is not None:
        # the verdict AND its evidence: raw alternating windows + pair
        # ratios, so a refusal is an auditable fact, not a missing row
        row["ab"] = {k: ab[k] for k in
                     ("speedup", "pair_ratios", "default_windows",
                      "candidate_windows", "min_speedup", "accepted",
                      "refusal_reason")}
    if doc.get("record_path"):
        row["record_committed"] = True
    if not quiet:
        print(json.dumps({k: row[k] for k in ("tunable", "status",
                                              "winner")}
                         | ({"speedup": ab["speedup"]} if ab else {}),
              ), flush=True)
    return row


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--target", default="all",
                    choices=["all"] + sorted(HOST_TUNABLES))
    ap.add_argument("--smoke", action="store_true",
                    help="seconds-fast path check (tiny sizes, capped "
                         "budget); does not overwrite the committed "
                         "results file")
    ap.add_argument("--cache-dir", default=None,
                    help="persist winners here for later replay "
                         "(default: throwaway tmp dir)")
    ap.add_argument("--json", default=None,
                    help="results path (default: benchmark/"
                         "autotune_results.json; smoke runs only write "
                         "when given explicitly)")
    args = ap.parse_args()

    names = sorted(HOST_TUNABLES) if args.target == "all" \
        else [args.target]
    tmp = None
    store_dir = args.cache_dir
    if store_dir is None:
        tmp = tempfile.TemporaryDirectory(prefix="pt-autotune-")
        store_dir = tmp.name

    rows = [run_one(n, store_dir, smoke=args.smoke) for n in names]

    out_path = args.json or (None if args.smoke else RESULTS_PATH)
    if out_path:
        from input_pipeline import host_parallel_efficiency
        doc = {
            "description": "persistent-autotuner A/B: tuned-vs-default "
                           "per host-side tunable (search -> paired "
                           "alternating windows, median of per-pair "
                           "ratios, noise-gate verdicts committed "
                           "verbatim with raw windows)",
            "platform": __import__("jax").devices()[0].platform,
            "cpu_count": os.cpu_count(),
            "host_parallel_efficiency": host_parallel_efficiency(),
            "min_speedup_gate": 1.10,
            "rows": rows,
        }
        with open(out_path, "w") as f:
            json.dump(doc, f, indent=1)
        print(f"# wrote {out_path}", file=sys.stderr)
    if tmp is not None:
        tmp.cleanup()
    return rows


if __name__ == "__main__":
    main()

"""Continuous-batching incremental decode tests (ISSUE 16 tentpole):

* the BIT-identity oracle — incremental cached decode through the
  compiled one-token program must equal a full recompute-per-token
  replay (reset slabs -> re-prefill -> re-decode the prefix) through the
  SAME compiled programs, bit-for-bit, under slot churn and relocation;
* the zero-retrace contract — steady-state serving with per-step
  admit/evict and mixed lengths compiles NOTHING after warmup
  (retrace_guard + CompileStats counter deltas);
* fault injection at ``serving.decode_step`` — transient retries leave
  the KV slabs clean (token-identical to an uninjected run), fatal
  fails the affected actives with typed errors, keeps queued requests
  alive, and feeds the circuit breaker;
* the Server front door (``add_decode_model``/``submit_decode``) and the
  benchmark gate (smoke arm in-process; full A/B @slow).
"""
import json
import os

import numpy as np
import pytest

from paddle_tpu.faults import InjectedFault, ModelUnavailable
from paddle_tpu.serving.decode import (DecodeEngine, DecodeRuntime,
                                       bucket_for_len)
from paddle_tpu.serving.server import ModelError

RESULTS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark", "decode_results.json")


def _engine(vocab=23, hidden=12, layers=2, slots=3, seed=5, name="t"):
    return DecodeEngine(vocab, hidden_dim=hidden, n_layers=layers,
                        slots=slots, max_len=16, len_buckets=(16,),
                        eos_id=None, seed=seed, name=name)


def _bits(a):
    return np.ascontiguousarray(np.asarray(a, np.float32)).view(np.uint32)


def _greedy(eng, slot, prompt, n_steps, cohab=None, churn_at=None,
            churn_prompt=None):
    """Drive the engine by hand: prefill ``prompt`` into ``slot``
    (plus optional co-resident prompts advanced in lockstep), greedy-
    decode ``n_steps``; optionally EVICT the first cohab slot at step
    ``churn_at`` and admit ``churn_prompt`` there mid-flight.  Returns
    (tokens, [n_steps+1, V] logit rows) for ``slot``."""
    S = eng.slots
    eng.reset()
    cur = np.zeros(S, np.int64)
    lens = np.zeros(S, np.int32)
    act = np.zeros(S, np.float32)
    tok, row = eng.prefill(slot, prompt)
    rows, toks = [row], [tok]
    cur[slot], lens[slot], act[slot] = tok, len(prompt), 1.0
    for s, p in (cohab or {}).items():
        t2, _ = eng.prefill(s, p)
        cur[s], lens[s], act[s] = t2, len(p), 1.0
    for k in range(n_steps):
        if churn_at is not None and k == churn_at:
            victim = next(iter(cohab))
            act[victim] = 0.0                      # evict mid-flight
            t3, _ = eng.prefill(victim, churn_prompt)
            cur[victim], lens[victim] = t3, len(churn_prompt)
            act[victim] = 1.0                      # admit into the hole
        logits = eng.decode_step(cur, lens, act)
        for s in range(S):
            if act[s]:
                nxt = int(np.asarray(logits[s, 0]).argmax())
                if s == slot:
                    rows.append(np.asarray(logits[s, 0], np.float32))
                    toks.append(nxt)
                cur[s] = nxt
                lens[s] += 1
    return toks, np.stack(rows)


def test_bucket_for_len():
    assert bucket_for_len(5, (32, 64)) == 32
    assert bucket_for_len(33, (32, 64)) == 64
    assert bucket_for_len(64, (32, 64)) == 64
    # overflow: one oversized engine beats a rejected workload
    assert bucket_for_len(65, (32, 64)) == 65


def test_incremental_decode_matches_recompute_oracle():
    """THE correctness pin: the incremental path reuses cache slabs
    across every step; the oracle rebuilds them from zero for each
    token (reset -> prefill -> replay the recorded prefix through the
    same compiled one-token program) and must land on bitwise-equal
    logits.  The incremental run additionally carries a co-resident
    sequence that is evicted and REPLACED mid-flight (slot churn), and
    the oracle replays in a DIFFERENT slot with different neighbors
    (relocation invariance) — per-row bits must not notice any of it."""
    eng = _engine(name="oracle")
    prompt, n = [3, 7, 1, 9], 6
    toks, rows = _greedy(eng, 0, prompt, n, cohab={1: [2, 5]},
                         churn_at=3, churn_prompt=[8, 8, 4])
    assert len(toks) == n + 1 and rows.shape == (n + 1, eng.vocab_size)
    # greedy chain really is the argmax chain
    assert toks == [int(r.argmax()) for r in rows]

    for t in range(n + 1):
        # full recompute of step t in another slot with another neighbor
        eng.reset()
        first, row = eng.prefill(2, prompt)
        eng.prefill(0, [6, 2, 2, 1, 5])
        assert first == toks[0]
        if t == 0:
            replay = row
        else:
            cur = np.zeros(eng.slots, np.int64)
            lens = np.zeros(eng.slots, np.int32)
            act = np.zeros(eng.slots, np.float32)
            lens[2], act[2] = len(prompt), 1.0
            for k in range(t):
                cur[2] = toks[k]
                logits = eng.decode_step(cur, lens, act)
                lens[2] += 1
            replay = np.asarray(logits[2, 0], np.float32)
        np.testing.assert_array_equal(
            _bits(replay), _bits(rows[t]),
            err_msg=f"recompute oracle diverged at token step {t}")


def test_decode_rows_independent_of_coresidents():
    """Same engine, same prompt: solo vs fully-packed pool produce
    bit-identical logit rows AND tokens (the property that makes
    continuous batching invisible to the math)."""
    eng = _engine(name="indep")
    prompt = [4, 11, 2]
    toks_solo, rows_solo = _greedy(eng, 1, prompt, 5)
    toks_full, rows_full = _greedy(eng, 1, prompt, 5,
                                   cohab={0: [9, 1], 2: [6, 6, 6, 3]})
    assert toks_solo == toks_full
    np.testing.assert_array_equal(_bits(rows_solo), _bits(rows_full))


def test_steady_state_decode_zero_retrace():
    """After warmup the pool serves mixed prompt lengths, mixed
    generation lengths, and per-step admit/evict churn through EXACTLY
    two compiled programs: no new trace, no new cache entry."""
    from paddle_tpu.core import compile_cache

    eng = _engine(vocab=13, hidden=8, layers=1, slots=2, name="zrt")
    rt = DecodeRuntime(eng, step_wait_ms=0.5, default_deadline_ms=None)
    rt.start(warmup=True)
    try:
        c0 = dict(compile_cache.stats().counters)
        with compile_cache.retrace_guard():
            reqs = [rt.submit([1 + (i % 7), 2, 3][: 1 + (i % 3)],
                              1 + (i % 5)) for i in range(9)]
            outs = [r.result(timeout=120.0) for r in reqs]
        c1 = dict(compile_cache.stats().counters)
    finally:
        rt.shutdown(drain=True, timeout=60.0)
    for i, o in enumerate(outs):
        assert len(o["tokens"]) == 1 + (i % 5)
        assert o["finish"] == "length"
    assert c1.get("traces", 0) == c0.get("traces", 0)
    assert c1.get("misses", 0) == c0.get("misses", 0)


def test_decode_step_transient_fault_is_invisible():
    """A transient injected INSIDE the retry rim (before the executor
    call: slabs untouched) retries per the pool's policy and the run's
    tokens stay identical to an uninjected run."""
    from paddle_tpu.testing import faultinject as fi

    eng = _engine(vocab=19, hidden=8, layers=1, slots=2, name="fit")
    rt = DecodeRuntime(eng, step_wait_ms=0.5, default_deadline_ms=None)
    rt.start(warmup=True)
    trace = [([2, 9], 4), ([5, 1, 7], 3), ([8], 5)]
    try:
        base = [r.result(timeout=60.0)["tokens"]
                for r in [rt.submit(p, m) for p, m in trace]]
        fi.configure("serving.decode_step@2=transient")
        inj = [r.result(timeout=60.0)["tokens"]
               for r in [rt.submit(p, m) for p, m in trace]]
        assert fi.fired("serving.decode_step") == 1
        assert inj == base
        assert rt.breaker_state() == "closed"
    finally:
        fi.clear()
        rt.shutdown(drain=True, timeout=60.0)


def test_decode_step_fatal_fault_breaker_and_recovery():
    """A fatal at the decode step fails the ACTIVE sequence with a typed
    error, leaves the queued request alive, opens the breaker at its
    threshold (admission refused with ModelUnavailable), and the
    cooldown probe recovers — all on one pool."""
    import time

    from paddle_tpu.testing import faultinject as fi

    eng = _engine(vocab=19, hidden=8, layers=1, slots=1, name="fif")
    rt = DecodeRuntime(eng, step_wait_ms=0.5, default_deadline_ms=None,
                       breaker_threshold=1, breaker_cooldown_s=0.3)
    rt.start(warmup=True)
    try:
        fi.configure("serving.decode_step@1=fatal")
        r1 = rt.submit([2, 9], 4)          # admitted into the only slot
        r2 = rt.submit([5, 1, 7], 3)       # queued behind it
        with pytest.raises(ModelError):
            r1.result(timeout=60.0)
        assert fi.fired("serving.decode_step") == 1
        fi.clear()
        # breaker open: admission rejects new work with the typed error
        assert rt.breaker_state() == "open"
        with pytest.raises(ModelUnavailable):
            rt.submit([3, 3], 2)
        # the queued request survives the incident: after cooldown the
        # probe admits it and it completes normally
        out = r2.result(timeout=60.0)
        assert len(out["tokens"]) == 3 and out["finish"] == "length"
        deadline = time.monotonic() + 5.0
        while rt.breaker_state() != "closed" \
                and time.monotonic() < deadline:
            time.sleep(0.02)
        assert rt.breaker_state() == "closed"
        assert len(rt.submit([4], 2).result(timeout=60.0)["tokens"]) == 2
    finally:
        fi.clear()
        rt.shutdown(drain=True, timeout=60.0)
    assert isinstance(r1.error, (ModelError, InjectedFault))


def test_server_decode_front_door():
    """add_decode_model / submit_decode: the Server owns the pool's
    lifecycle, surfaces its health, and rejects after shutdown."""
    from paddle_tpu.serving import Server

    eng = _engine(vocab=19, hidden=8, layers=1, slots=2, name="srv")
    srv = Server(deadline_ms=None)
    srv.add_decode_model(eng, name="gen")
    srv.start()
    try:
        outs = [srv.submit_decode([2, 9, 4], 3, model="gen")
                .result(timeout=60.0) for _ in range(3)]
        assert all(o["tokens"] == outs[0]["tokens"] for o in outs)
        h = srv.health()
        assert h["decode"]["gen"]["served"] >= 3
        assert h["decode"]["gen"]["mode"] == "continuous"
    finally:
        srv.shutdown(drain=True)
    from paddle_tpu.faults import ServerClosed
    with pytest.raises(ServerClosed):
        srv.submit_decode([1], 1, model="gen")


def test_decode_bench_smoke_row_complete():
    from benchmark.decode import run_all

    row = run_all(smoke=True, quiet=True)
    assert row["smoke"] is True
    ab = row["ab"]
    assert len(ab["pair_ratios"]) >= 2
    assert len(ab["default_windows"]) == len(ab["candidate_windows"])
    assert ab["accepted"] in (True, False)
    if not ab["accepted"]:
        assert ab["refusal_reason"]
    for arm in ("static", "continuous"):
        r = row[arm]
        assert r["mode"] == arm
        assert r["decode_tokens_per_s"] > 0
        assert r["ttft_ms"]["p99"] >= r["ttft_ms"]["p50"]
        assert r["inter_token_ms"]["p99"] >= r["inter_token_ms"]["p50"]
        assert 0 < r["slot_occupancy"] <= 1
    # the schedulers must be invisible to the math
    assert row["arms_tokens_identical"] is True
    doc = row["doctor"]
    assert doc and "error" not in doc, doc
    assert doc["steps"] > 0 and doc["top"] in ("dispatch", "scheduler")


def test_committed_decode_results_structure():
    """The committed JSON carries real CPU rows (accepted at the 1.3x
    bar or an explicit refusal WITH raw windows) + the pending-hardware
    TPU stub."""
    with open(RESULTS) as fh:
        data = json.load(fh)
    assert data["benchmark"] == "decode_continuous_batching"
    cpu = data["cpu"]
    ab = cpu["ab"]
    assert ab["min_speedup"] == 1.3
    assert ab["accepted"] or ab["refusal_reason"]
    assert ab["default_windows"] and ab["candidate_windows"]
    assert cpu["arms_tokens_identical"] is True
    assert cpu["continuous"]["decode_tokens_per_s"] > 0
    assert cpu["static"]["decode_tokens_per_s"] > 0
    assert cpu["doctor"]["steps"] > 0
    assert data["tpu"]["status"] == "pending-hardware"


@pytest.mark.slow
def test_decode_full_ab_runs():
    from benchmark.decode import run_all

    row = run_all(smoke=False, quiet=True)
    assert row["arms_tokens_identical"] is True
    assert row["doctor"]["steps"] > 0

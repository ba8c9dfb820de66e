"""End-to-end tracing + perf attribution (ISSUE 10 acceptance).

Pins:

* span API semantics: nesting, cross-thread parents, one-trace-per-
  request ROOT sentinel, events, frozen names;
* tracing OFF path: zero JSONL events, zero registry writes, zero
  retraces with ``observe`` off even when a metrics_log is set;
* span parent/child invariants on a REAL pipelined run: every parent
  exists, no cycles, the whole chain joins one trace, step events carry
  their span join keys;
* the doctor: budget components sum to the measured wall within the
  pinned tolerance, calibration rows, trace/doctor/stats CLIs including
  multi-file merge with restart boundaries;
* serving: request spans nest inside their batch's dispatch window,
  batch spans link member traces, retry/breaker span events survive a
  drain;
* robustness: torn/truncated final JSONL line (chaos-kill artifact) is
  counted, never fatal;
* Prometheus exposition: name-mangling round trip against METRIC_NAMES.
"""
import json
import threading

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import flags, layers
from paddle_tpu import observability as obs
from paddle_tpu.core.compile_cache import retrace_guard
from paddle_tpu.observability import export as obs_export
from paddle_tpu.observability import tracing


@pytest.fixture(autouse=True)
def clean_observability():
    obs.registry().reset()
    prev = {n: flags.get_flag(n) for n in ("observe", "metrics_log")}
    yield
    for n, v in prev.items():
        flags.set_flag(n, v if v is not None else "")
    obs_export._reset_writer()
    obs.registry().reset()


def _read_events(path):
    events, _files = obs_export.iter_log_events([str(path)])
    return events


def _spans(events):
    return [e for e in events if e.get("kind") == "span"]


def _assert_tree_invariants(spans):
    """Every span's parent exists inside its trace; parent chains
    terminate (no cycles); trace ids agree along edges."""
    by_id = {e["span"]: e for e in spans}
    for e in spans:
        p = e.get("parent")
        if p is None:
            continue
        assert p in by_id, f"span {e['span']} has unknown parent {p}"
        assert by_id[p]["trace"] == e["trace"], \
            f"parent {p} in different trace"
        seen, cur = set(), e
        while cur.get("parent"):
            assert cur["span"] not in seen, f"cycle through {cur['span']}"
            seen.add(cur["span"])
            cur = by_id[cur["parent"]]


# ---------------------------------------------------------------------------
# span API (no jax)
# ---------------------------------------------------------------------------
def test_span_api_nesting_events_and_cross_thread_parent(tmp_path):
    flags.set_flag("metrics_log", str(tmp_path / "api.jsonl"))
    with tracing.span("executor/run_pipelined", steps_per_dispatch=4) as root:
        assert tracing.current_span() is root
        with tracing.span("executor/step", path="run") as child:
            assert child.parent_id == root.span_id
            assert child.trace_id == root.trace_id
            tracing.add_event("retry", attempt=1)
        # cross-thread: explicit parent, ended on the other thread
        done = threading.Event()

        def worker():
            sp = tracing.start_span("pipeline/stage", parent=root,
                                    kind="scan")
            sp.end(steps=4)
            done.set()

        threading.Thread(target=worker).start()
        assert done.wait(5)
        # ROOT forces a fresh trace even under an active span
        iso = tracing.start_span("serving/request", parent=tracing.ROOT,
                                 id=1)
        assert iso.parent_id is None and iso.trace_id != root.trace_id
        iso.cancel()                       # cancelled spans never emit
    assert tracing.current_span() is None
    spans = _spans(_read_events(tmp_path / "api.jsonl"))
    names = [e["name"] for e in spans]
    assert sorted(names) == ["executor/run_pipelined", "executor/step",
                             "pipeline/stage"]
    _assert_tree_invariants(spans)
    step = next(e for e in spans if e["name"] == "executor/step")
    assert step["events"][0]["name"] == "retry"
    stage = next(e for e in spans if e["name"] == "pipeline/stage")
    assert stage["labels"] == {"kind": "scan", "steps": 4}  # end() merges


def test_span_names_frozen():
    with pytest.raises(KeyError, match="frozen"):
        tracing.start_span("executor/step_tmie")          # typo'd
    # idempotent end: second end() emits nothing
    flags.set_flag("metrics_log", "")
    sp = tracing.start_span("reader/item", parent=tracing.ROOT)
    sp.end()
    sp.end()


# ---------------------------------------------------------------------------
# zero overhead when off (acceptance-pinned)
# ---------------------------------------------------------------------------
def _build_net():
    x = layers.data("x", shape=[8], dtype="float32")
    y = layers.data("y", shape=[1], dtype="int64")
    h = layers.fc(x, size=16, act="relu")
    pred = layers.fc(h, size=3, act="softmax")
    loss = layers.mean(layers.cross_entropy(pred, y))
    pt.optimizer.SGD(learning_rate=0.1).minimize(loss)
    return loss


def _batches(n, batch=16):
    rng = np.random.RandomState(0)
    return [{"x": rng.rand(batch, 8).astype("float32"),
             "y": rng.randint(0, 3, (batch, 1))} for _ in range(n)]


def test_tracing_off_zero_events_zero_writes_zero_retrace(tmp_path):
    """observe off + metrics_log SET: the training path emits NO JSONL
    events (spans included), writes NOT A BYTE to the log, touches NO
    metrics, and cannot retrace.  The one thing a metrics log gets whatever
    ``observe`` says is what made a step COLD (one ``phase`` event a record
    of the phase log, core/compile_cache.py; ``metrics_log``'s help says
    so): exactly five for each of the three steps here, all of them
    written before the first warm dispatch."""
    log = tmp_path / "off.jsonl"
    flags.set_flag("observe", False)
    flags.set_flag("metrics_log", str(log))
    loss = _build_net()
    prog = pt.default_main_program()
    exe = pt.Executor()
    exe.run(pt.default_startup_program(), feed={}, fetch_list=[])
    feeds = _batches(17)
    before = obs.registry().snapshot()
    exe.run(feed=feeds[0], fetch_list=[loss])       # pays the one trace
    with retrace_guard():
        cold = list(exe.run_pipelined(      # the K-step scan's one trace
            iter(feeds[1:9]), prog, fetch_list=[loss],
            steps_per_dispatch=4))
        written = log.read_bytes()
        # every step is warm from here on: zero events, zero writes
        for feed in feeds[1:9]:
            exe.run(feed=feed, fetch_list=[loss])
        warm = list(exe.run_pipelined(
            iter(feeds[9:]), prog, fetch_list=[loss],
            steps_per_dispatch=4))
    assert len(cold) == len(warm) == 8
    assert obs.registry().snapshot() == before
    assert log.read_bytes() == written
    events = [json.loads(ln) for ln in written.decode().splitlines()]
    assert {e["kind"] for e in events} <= {"identity", "phase"}
    # startup, run and the K-step scan went cold once each
    assert len([e for e in events if e["kind"] == "phase"]) == 15


# ---------------------------------------------------------------------------
# pipelined run: invariants + doctor + CLIs (one run, many assertions)
# ---------------------------------------------------------------------------
def test_pipelined_trace_invariants_doctor_and_clis(tmp_path, capsys):
    log = tmp_path / "run.jsonl"
    flags.set_flag("observe", True)
    flags.set_flag("metrics_log", str(log))
    loss = _build_net()
    prog = pt.default_main_program()
    exe = pt.Executor()
    exe.run(pt.default_startup_program(), feed={}, fetch_list=[])
    with retrace_guard():       # spans may not retrace either
        list(exe.run_pipelined(iter(_batches(10)), prog,
                               fetch_list=[loss], steps_per_dispatch=4))
        list(exe.run_pipelined(iter(_batches(10)), prog,
                               fetch_list=[loss], steps_per_dispatch=4))
    flags.set_flag("metrics_log", "")

    events = _read_events(log)
    spans = _spans(events)
    _assert_tree_invariants(spans)
    names = {e["name"] for e in spans}
    assert {"executor/run_pipelined", "reader/pipeline", "reader/item",
            "pipeline/stage", "executor/step", "executor/dispatch",
            "executor/fetch_block"} <= names
    # the whole causal chain joins ONE trace per run_pipelined call
    roots = [e for e in spans if e["name"] == "executor/run_pipelined"]
    assert len(roots) == 2
    for root in roots:
        members = [e for e in spans if e["trace"] == root["trace"]]
        mnames = {e["name"] for e in members}
        assert {"pipeline/stage", "executor/step", "reader/item",
                "executor/dispatch"} <= mnames
    # step events carry their span join keys
    step_events = [e for e in events if e.get("kind") == "step"]
    ids = {e["span"] for e in spans}
    for se in step_events:
        assert se["span"] in ids and se["trace"]

    # ---- doctor: budget sums to measured wall within tolerance ----
    from paddle_tpu.observability import attribution
    budget = attribution.step_budget(events)
    assert budget is not None and budget["within_tolerance"]
    total = sum(budget["budget"].values())
    wall = budget["measured_wall_ms"]
    assert abs(total - wall) <= attribution.BUDGET_TOLERANCE * wall
    assert budget["steps"] == 21       # startup-program run + 2x10
    assert budget["top"] in budget["budget"]
    assert budget["hints"]

    # ---- build_traces / span_stats / critical path ----
    traces = tracing.build_traces(events)
    big = max(traces, key=lambda t: len(t["spans"]))
    assert tracing.critical_path(big)[0]["name"] == \
        "executor/run_pipelined"
    stats = tracing.span_stats(events)
    assert stats["executor/step"]["count"] >= 4

    # ---- multi-file merge: split the log, feed both halves ----
    lines = log.read_text().splitlines()
    a, b = tmp_path / "a.jsonl", tmp_path / "b.jsonl"
    a.write_text("\n".join(lines[: len(lines) // 2]) + "\n")
    b.write_text("\n".join(lines[len(lines) // 2:]) + "\n")
    merged = obs_export.summarize_logs([str(a), str(b)])
    single = obs_export.summarize_logs([str(log)])
    assert merged["events"] == single["events"]
    assert merged["steps"]["steps"] == single["steps"]["steps"]
    assert len(merged["restarts"]) == 2
    assert "restart boundary" in obs_export.render_summary(merged)

    # ---- CLIs: stats (multi-file), trace, doctor (+ --program) ----
    from paddle_tpu.cli import main as cli_main
    assert cli_main(["stats", str(a), str(b)]) == 0
    out = capsys.readouterr().out
    assert "restart boundary" in out

    assert cli_main(["trace", str(a), str(b), "--limit", "1"]) == 0
    out = capsys.readouterr().out
    assert "executor/run_pipelined" in out and "critical path" in out

    prog_json = tmp_path / "prog.json"
    prog_json.write_text(prog.to_json())
    cal_out = tmp_path / "calibration.json"
    assert cli_main(["doctor", str(a), str(b),
                     "--program", str(prog_json), "--batch", "16",
                     "--calibration-out", str(cal_out)]) == 0
    out = capsys.readouterr().out
    doc = json.loads(out.strip().splitlines()[-1])
    assert doc["training"]["within_tolerance"]
    assert doc["calibration"]["ratio"] > 0
    table = json.loads(cal_out.read_text())
    assert doc["calibration"]["program"] in table["programs"]


def test_calibration_table_merges_by_program(tmp_path):
    from paddle_tpu.observability import attribution
    path = str(tmp_path / "cal.json")
    r1 = {"program": "aaa", "predicted_ms": 1.0, "measured_ms": 2.0,
          "ratio": 2.0}
    r2 = {"program": "bbb", "predicted_ms": 1.0, "measured_ms": 3.0,
          "ratio": 3.0}
    attribution.save_calibration([r1], path)
    doc = attribution.save_calibration([r2, {**r1, "ratio": 4.0}], path)
    assert set(doc["programs"]) == {"aaa", "bbb"}
    assert doc["programs"]["aaa"]["ratio"] == 4.0   # re-doctor overwrites


@pytest.mark.parametrize("older", [
    {"format": 2, "programs": {"aaa": {"program": "aaa", "ratio": 2.0}},
     "op_classes": {"aaa:mul": {"program": "aaa", "op_type": "mul",
                                "ratio": 1.5}}},
    {"format": 1, "programs": {"aaa": {"program": "aaa", "ratio": 2.0}}},
    {"aaa": {"program": "aaa", "ratio": 2.0}},
], ids=["op_class_rows", "format_1", "bare_map"])
def test_calibration_table_of_an_older_tree_still_merges(tmp_path, older):
    """A table an older tree wrote, op-class rows included, loads: its
    program rows merge with the new one and any other section is
    dropped."""
    from paddle_tpu.observability import attribution
    path = tmp_path / "cal.json"
    path.write_text(json.dumps(older))
    doc = attribution.save_calibration(
        [{"program": "bbb", "ratio": 3.0}], str(path))
    assert doc == json.loads(path.read_text())
    assert set(doc) == {"format", "programs"}
    assert set(doc["programs"]) == {"aaa", "bbb"}


def test_executable_facts_via_compat():
    """cost_analysis()/memory_analysis() guarded through compat: on this
    jax a compiled step exposes flops; the wrapper never raises."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu import compat
    from paddle_tpu.observability import attribution
    comp = jax.jit(lambda x: jnp.dot(x, x)).lower(
        jnp.ones((32, 32), jnp.float32)).compile()
    facts = attribution.executable_facts(comp)
    assert facts is not None and facts["flops"] > 0
    assert compat.executable_cost_analysis(object()) is None
    assert compat.executable_memory_analysis(object()) is None


# ---------------------------------------------------------------------------
# serving: spans, budget, fault events under drain
# ---------------------------------------------------------------------------
def _fake_model(name="toy", fn=None):
    from paddle_tpu.serving import Model
    return Model(name, fn or (lambda feeds: [np.asarray(feeds["x"]) * 2.0]),
                 example={"x": np.zeros(2, "float32")})


def test_serving_request_batch_spans_and_budget(tmp_path):
    from paddle_tpu.serving import Server
    log = tmp_path / "serve.jsonl"
    flags.set_flag("metrics_log", str(log))
    srv = Server(max_batch=4, max_wait_ms=2, deadline_ms=None,
                 warmup=False)
    srv.add_model(_fake_model())
    srv.start()
    try:
        for i in range(6):
            srv.infer({"x": np.ones(2, "float32") * i}, timeout=10)
    finally:
        srv.shutdown()
    flags.set_flag("metrics_log", "")
    events = _read_events(log)
    spans = _spans(events)
    reqs = [e for e in spans if e["name"] == "serving/request"]
    batches = [e for e in spans if e["name"] == "serving/batch"]
    assert len(reqs) == 6 and batches
    # one trace per request; batch spans link member request traces and
    # every member request's completion lands inside its batch window
    assert len({e["trace"] for e in reqs}) == 6
    by_id = {(e.get("labels") or {}).get("id"): e for e in reqs}
    linked = set()
    for b in batches:
        labels = b["labels"]
        assert labels["traces"]
        b_end = b["t0"] + b["dur_ms"] / 1e3
        for rid in labels["requests"]:
            r = by_id[rid]
            r_end = r["t0"] + r["dur_ms"] / 1e3
            assert b["t0"] - 1e-6 <= r_end <= b_end + 1e-6
            linked.add(rid)
    assert linked == set(by_id)
    assert all((e.get("labels") or {}).get("status") == "ok"
               for e in reqs)

    from paddle_tpu.observability import attribution
    sb = attribution.serving_budget(events)
    assert sb["served"] == 6 and sb["within_tolerance"]
    assert sb["budget"]["dispatch_ms_mean"] is not None


def test_retry_and_breaker_span_events_survive_drain(tmp_path):
    """Chaos round: a transient dispatch failure leaves a `retry` span
    event, repeated fatal batches leave a `breaker_open` span event, and
    both survive a drain-to-stopped shutdown (the SIGTERM handler path —
    serving/cli.py wires SIGTERM to exactly this drain; the subprocess
    round lives in the @slow chaos suite)."""
    from paddle_tpu import faults
    from paddle_tpu.serving import Server
    log = tmp_path / "chaos.jsonl"
    flags.set_flag("metrics_log", str(log))

    flaky_calls = {"n": 0}

    def flaky(feeds):
        flaky_calls["n"] += 1
        if flaky_calls["n"] == 1:
            raise faults.TransientDispatchError("injected transient")
        return [np.asarray(feeds["x"]) * 2.0]

    def poisoned(feeds):
        raise ValueError("poisoned tenant")

    srv = Server(max_batch=2, max_wait_ms=1, deadline_ms=None,
                 warmup=False, breaker_threshold=2)
    srv.add_model(_fake_model("flaky", flaky))
    srv.add_model(_fake_model("bad", poisoned))
    srv.start()
    try:
        # transient -> retried inside the SAME batch span
        out = srv.infer({"x": np.ones(2, "float32")}, model="flaky",
                        timeout=10)
        assert np.allclose(out[0], 2.0)
        # two fatal batches -> breaker opens on the second
        for _ in range(2):
            p = srv.submit({"x": np.ones(2, "float32")}, model="bad")
            with pytest.raises(Exception):
                p.result(timeout=10)
        # breaker now open: the rejection is traced too
        with pytest.raises(faults.ModelUnavailable):
            srv.submit({"x": np.ones(2, "float32")}, model="bad")
    finally:
        srv.begin_drain()
        srv.shutdown()           # drain: every admitted request answered
    flags.set_flag("metrics_log", "")

    events = _read_events(log)
    spans = _spans(events)
    batch_events = [ev for e in spans if e["name"] == "serving/batch"
                    for ev in e.get("events", [])]
    assert any(ev["name"] == "retry" for ev in batch_events)
    assert any(ev["name"] == "breaker_open" for ev in batch_events)
    # drain left no un-terminated request span: every submit (including
    # the breaker-open rejection) emitted a terminal span
    reqs = [e for e in spans if e["name"] == "serving/request"]
    assert len(reqs) == 4
    assert any((e.get("labels") or {}).get("status") == "ModelUnavailable"
               for e in reqs)
    states = [str(e.get("state")) for e in events
              if e.get("kind") == "serving" and e.get("event") == "state"]
    assert states[-2:] == ["draining", "stopped"]


def test_rejected_request_span_carries_typed_status(tmp_path):
    """Admission rejections (Overloaded backpressure) still emit the
    request span with the typed status — shed requests are exactly what
    an overload trace must show (regression: rejection paths used to
    raise without ever ending the span)."""
    from paddle_tpu import faults
    from paddle_tpu.serving import Server
    log = tmp_path / "reject.jsonl"
    flags.set_flag("metrics_log", str(log))
    gate = threading.Event()

    def slow(feeds):
        gate.wait(10)
        return [np.asarray(feeds["x"]) * 2.0]

    srv = Server(max_batch=1, max_wait_ms=1, deadline_ms=None,
                 queue_capacity=1, shed=False, warmup=False,
                 staging_depth=1)
    srv.add_model(_fake_model("slow", slow))
    srv.start()
    admitted, rejected = [], 0
    try:
        # soak dispatcher + staging + queue, then keep offering until
        # the bounded queue rejects (backpressure, shed=False)
        for _ in range(12):
            try:
                admitted.append(srv.submit({"x": np.ones(2, "float32")}))
            except faults.Overloaded:
                rejected += 1
        assert rejected >= 1 and admitted
    finally:
        gate.set()
        srv.shutdown()
    flags.set_flag("metrics_log", "")
    reqs = [e for e in _spans(_read_events(log))
            if e["name"] == "serving/request"]
    statuses = {(e.get("labels") or {}).get("status") for e in reqs}
    assert "Overloaded" in statuses and "ok" in statuses
    # every admitted-or-rejected request reached a terminal span
    assert len(reqs) == len(admitted) + rejected


@pytest.mark.parametrize("path", ["run", "run_steps"])
def test_failed_dispatch_still_emits_step_span(tmp_path, path):
    """A fatally failing dispatch ends the executor/step root with the
    typed status instead of leaving its dispatch child orphaned — on
    both paths through the one observed dispatch."""
    from paddle_tpu.testing import faultinject
    log = tmp_path / "fail.jsonl"
    flags.set_flag("observe", True)
    flags.set_flag("metrics_log", str(log))
    loss = _build_net()
    exe = pt.Executor()
    exe.run(pt.default_startup_program(), feed={}, fetch_list=[])
    faultinject.configure("executor.dispatch@*=error")
    try:
        with pytest.raises(Exception, match="injected"):
            if path == "run":
                exe.run(feed=_batches(1)[0], fetch_list=[loss])
            else:
                exe.run_steps(2, feed=_batches(1)[0], fetch_list=[loss])
    finally:
        faultinject.clear()
        flags.set_flag("metrics_log", "")
    spans = _spans(_read_events(log))
    _assert_tree_invariants(spans)
    failed = [e for e in spans if e["name"] == "executor/step"
              and (e.get("labels") or {}).get("status") == "InjectedFault"
              and (e.get("labels") or {}).get("path") == path]
    assert failed, f"no failed step span in {[e['name'] for e in spans]}"


def test_executor_retry_span_event(tmp_path):
    """A transient dispatch failure at the executor rim records a retry
    span event on the dispatch span."""
    from paddle_tpu import faults
    from paddle_tpu.testing import faultinject
    log = tmp_path / "retry.jsonl"
    flags.set_flag("observe", True)
    flags.set_flag("metrics_log", str(log))
    loss = _build_net()
    exe = pt.Executor(retry_policy=faults.RetryPolicy(
        max_attempts=2, backoff_base_s=0.0, jitter=0.0))
    exe.run(pt.default_startup_program(), feed={}, fetch_list=[])
    faultinject.configure("executor.dispatch@1=transient")
    try:
        exe.run(feed=_batches(1)[0], fetch_list=[loss])
    finally:
        faultinject.clear()
        flags.set_flag("metrics_log", "")
    spans = _spans(_read_events(log))
    dispatch = [e for e in spans if e["name"] == "executor/dispatch"]
    assert any(ev["name"] == "retry"
               for e in dispatch for ev in e.get("events", []))


# ---------------------------------------------------------------------------
# robustness + prometheus
# ---------------------------------------------------------------------------
def test_truncated_final_line_counted_not_fatal(tmp_path):
    """A process killed mid-write tears the final line — possibly inside
    a multi-byte UTF-8 character.  The summary skips it with a counted
    warning instead of aborting (UnicodeDecodeError regression)."""
    p = tmp_path / "torn.jsonl"
    good = ('{"ts": 1.0, "kind": "step", "steps": 2, "step_ms": 3.0,'
            ' "wall_ms": 6.0}\n')
    torn = '{"ts": 2.0, "kind": "step", "label": "café'.encode()[:-1]
    p.write_bytes(good.encode() + torn)
    s = obs.summarize_log(str(p))
    assert s["corrupt_lines"] == 1
    assert s["steps"]["steps"] == 2
    # and a clean multi-file merge still reports the torn file's count
    q = tmp_path / "ok.jsonl"
    q.write_text(good)
    merged = obs_export.summarize_logs([str(p), str(q)])
    assert merged["corrupt_lines"] == 1 and merged["steps"]["steps"] == 4


def test_merged_fault_timeline_carries_source_index(tmp_path):
    """A relaunched job produces one log per attempt; the merged faults
    timeline interleaves them by coerced ts ONLY, so each rendered row
    must also carry the source-file index (argument position) — without
    it an event is not attributable to the right attempt."""
    a, b = tmp_path / "attempt0.jsonl", tmp_path / "attempt1.jsonl"
    def fault(ts, event, **kw):
        return json.dumps({"ts": ts, "kind": "fault",
                           "event": event, **kw}) + "\n"
    # attempt 1's first fault lands BETWEEN attempt 0's two faults on
    # the clock (overlapping supervisor/child shutdown) — exactly the
    # interleaving ts-order cannot disambiguate
    a.write_text(fault(1.0, "inject", site="dispatch", step=3)
                 + fault(3.0, "relaunch", attempt=1, delay_s=0.5))
    b.write_text(fault(2.0, "restore", step=3)
                 + fault(4.0, "inject", site="dispatch", step=7))
    merged = obs_export.summarize_logs([str(a), str(b)])
    tl = merged["faults"]["timeline"]
    assert [(e.get("source"), e["event"]) for e in tl] == \
        [(0, "inject"), (1, "restore"), (0, "relaunch"), (1, "inject")]
    # restart boundaries name the same index the rows carry
    assert [(r["source"], r["file"]) for r in merged["restarts"]] == \
        [(0, str(a)), (1, str(b))]
    text = obs_export.render_summary(merged)
    assert "source=1 event=restore" in text
    assert "[1] " + str(b) in text
    # single-file summaries stay unchanged: no source column
    single = obs_export.summarize_logs([str(a)])
    assert all("source" not in e for e in single["faults"]["timeline"])
    assert "source=" not in obs_export.render_summary(single)


def test_prometheus_name_mangling_round_trip():
    names = [n for n, _k, _h in obs.METRIC_NAMES]
    mangled = [obs_export.prom_name(n) for n in names]
    assert len(set(mangled)) == len(names)          # no collisions
    for n, m in zip(names, mangled):
        assert obs_export.metric_name_from_prom(m) == n
        # the reversibility invariant: subsystem part carries no "_"
        assert "_" not in n.split("/")[0]
    with pytest.raises(ValueError):
        obs_export.metric_name_from_prom("not_paddle")


def test_prometheus_exposition_and_stats_prom_cli(tmp_path, capsys):
    flags.set_flag("observe", True)
    flags.set_flag("metrics_log", str(tmp_path / "prom.jsonl"))
    obs.inc_counter("executor/steps", 3)
    obs.observe_hist("executor/step_time_ms", 4.0)
    obs.set_gauge("device/bytes_in_use", 10, label="cpu:0")
    text = obs_export.to_prometheus(obs.metrics_snapshot())
    assert "paddle_tpu_executor_steps_total 3" in text
    assert 'paddle_tpu_executor_step_time_ms_bucket{le="5"} 1' in text
    assert "paddle_tpu_executor_step_time_ms_count 1" in text
    assert 'paddle_tpu_device_bytes_in_use{label="cpu:0"} 10' in text
    obs.periodic_report(step=1)           # snapshot event for the CLI
    flags.set_flag("metrics_log", "")
    from paddle_tpu.cli import main as cli_main
    assert cli_main(["stats", str(tmp_path / "prom.jsonl"),
                     "--prom"]) == 0
    out = capsys.readouterr().out
    assert "paddle_tpu_executor_steps_total 3" in out
    # no snapshot in the log -> a one-line error, not a traceback
    (tmp_path / "empty.jsonl").write_text("")
    with pytest.raises(SystemExit, match="no snapshot"):
        cli_main(["stats", str(tmp_path / "empty.jsonl"), "--prom"])

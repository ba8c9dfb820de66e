"""Timing utilities: Stat/global_stat thread safety, compile_report, the reentrancy-guarded profiler() context
manager, and the merged report surface."""
import re
import threading
import time

import pytest

from paddle_tpu import profiler


# ---------------------------------------------------------------------------
# Stat
# ---------------------------------------------------------------------------
def test_stat_accumulates_and_reports():
    st = profiler.Stat()
    for _ in range(3):
        with st.timer("fwd"):
            pass
    with st.timer("bwd"):
        pass
    rep = st.report()
    assert "StatSet" in rep
    m = re.search(r"fwd: total=\S+ count=(\d+)", rep)
    assert m and int(m.group(1)) == 3
    assert "bwd" in rep
    st.reset()
    assert "fwd" not in st.report()


def test_stat_thread_safe_concurrent_timers():
    st = profiler.Stat()
    n_threads, n_iters = 8, 500

    def work():
        for _ in range(n_iters):
            with st.timer("x"):
                pass

    threads = [threading.Thread(target=work) for _ in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    m = re.search(r"x: total=\S+ count=(\d+)", st.report())
    assert m and int(m.group(1)) == n_threads * n_iters


def test_stat_report_survives_reset_race():
    """reset()/report() racing live timer() scopes must neither crash
    (dict-changed-size, ZeroDivisionError) nor deadlock."""
    st = profiler.Stat()
    stop = threading.Event()
    errors = []

    def churn():
        try:
            i = 0
            while not stop.is_set():
                with st.timer(f"op{i % 5}"):
                    pass
                i += 1
        except Exception as e:      # pragma: no cover - the failure mode
            errors.append(e)

    threads = [threading.Thread(target=churn) for _ in range(4)]
    for t in threads:
        t.start()
    deadline = time.monotonic() + 0.5
    try:
        while time.monotonic() < deadline:
            st.report()
            st.reset()
    finally:
        stop.set()
        for t in threads:
            t.join()
    assert not errors, errors


def test_global_stat_and_timer_helper():
    profiler.global_stat().reset()
    with profiler.timer("step"):
        pass
    assert "step" in profiler.global_stat().report()
    profiler.global_stat().reset()


# ---------------------------------------------------------------------------
# compile_report / merged report
# ---------------------------------------------------------------------------
def test_compile_report_is_stat_style_text():
    rep = profiler.compile_report()
    assert isinstance(rep, str) and "CompileStats" in rep


def test_merged_report_has_all_three_sections():
    profiler.global_stat().reset()
    with profiler.timer("mine"):
        pass
    rep = profiler.report()
    profiler.global_stat().reset()
    assert "StatSet" in rep and "mine" in rep
    assert "CompileStats" in rep
    assert "Metrics" in rep


def test_merged_report_leaves_out_an_empty_statset():
    # the program itself times nothing into the StatSet (its cold start is
    # in compile_stats()' phase log), so an untouched one prints no block
    profiler.global_stat().reset()
    rep = profiler.report()
    assert "StatSet" not in rep
    assert "CompileStats" in rep and "Metrics" in rep


def test_metrics_snapshot_reexport_shape():
    snap = profiler.metrics_snapshot()
    assert set(snap) == {"metrics", "compile", "device_memory"}
    assert all(k.startswith("compile/") for k in snap["compile"])


# ---------------------------------------------------------------------------
# profiler() context manager
# ---------------------------------------------------------------------------
@pytest.fixture
def fake_trace(monkeypatch):
    import jax
    calls = {"start": 0, "stop": 0}
    monkeypatch.setattr(
        jax.profiler, "start_trace",
        lambda *a, **k: calls.__setitem__("start", calls["start"] + 1))
    monkeypatch.setattr(
        jax.profiler, "stop_trace",
        lambda: calls.__setitem__("stop", calls["stop"] + 1))
    return calls


def test_profiler_ctx_nested_is_single_session(fake_trace):
    with profiler.profiler("/tmp/t1"):
        with profiler.profiler("/tmp/t2"):   # nested: no-op inner scope
            with profiler.profiler("/tmp/t3"):
                pass
        assert fake_trace == {"start": 1, "stop": 0}
    assert fake_trace == {"start": 1, "stop": 1}


def test_profiler_ctx_accepts_and_ignores_reference_args(fake_trace):
    with profiler.profiler("/tmp/t", state="GPU", sorted_key="total"):
        pass
    assert fake_trace == {"start": 1, "stop": 1}


def test_profiler_ctx_recovers_after_start_failure(fake_trace, monkeypatch):
    import jax
    fixture_fake = jax.profiler.start_trace   # the fake from fake_trace

    def boom(*a, **k):
        raise RuntimeError("collector busy")

    monkeypatch.setattr(jax.profiler, "start_trace", boom)
    with pytest.raises(RuntimeError, match="collector busy"):
        with profiler.profiler("/tmp/t"):
            pass                      # pragma: no cover - never reached
    # the failed enter must not leave a stuck depth: a later scope starts
    monkeypatch.setattr(jax.profiler, "start_trace", fixture_fake)
    with profiler.profiler("/tmp/t"):
        pass
    assert fake_trace == {"start": 1, "stop": 1}


def test_cuda_profiler_alias():
    assert profiler.cuda_profiler is profiler.profiler


def test_stat_timer_times_real_work():
    st = profiler.Stat()
    with st.timer("sleep"):
        time.sleep(0.01)
    m = re.search(r"sleep: total=(\S+)ms", st.report())
    assert m and float(m.group(1)) >= 8.0

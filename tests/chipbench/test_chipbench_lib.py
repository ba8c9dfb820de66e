"""The yardstick's arithmetic: peaks, FLOP counts, order statistics, the
open-loop generator."""
import importlib.util
import os
import threading
import time

import numpy as np
import pytest

from conftest import BENCH, load_json

from chipbench.lib import open_loop, peaks, stats


def _config(name):
    spec = importlib.util.spec_from_file_location(
        f"cfg_{name}", os.path.join(BENCH, "configs", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod, load_json(BENCH, "configs", f"{name}.json")


def test_peaks_table():
    row = peaks.peaks_for("TPU v5 lite")
    assert (row.bf16_flops, row.hbm_bytes_s, row.ici_bytes_s) == \
        (197e12, 819e9, 200e9)
    assert "Google Cloud" in row.source
    with pytest.raises(LookupError):
        peaks.peaks_for("TPU v9 imaginary")


def test_resnet50_flops_against_a_hand_count():
    mod, sizes = _config("resnet50")
    # by hand, 224^2, stride on the 3x3 convolution, in MACs:
    stem = 112 * 112 * 64 * 3 * 49
    def block(hw_in, hw_out, c_in, w, proj):
        return (hw_in * hw_in * w * c_in            # 1x1 before the stride
                + hw_out * hw_out * w * w * 9       # 3x3 carries the stride
                + hw_out * hw_out * 4 * w * w       # 1x1 expansion
                + (hw_out * hw_out * 4 * w * c_in if proj else 0))
    macs = stem
    macs += block(56, 56, 64, 64, True) + 2 * block(56, 56, 256, 64, False)
    macs += block(56, 28, 256, 128, True) + 3 * block(28, 28, 512, 128, False)
    macs += block(28, 14, 512, 256, True) + 5 * block(14, 14, 1024, 256, False)
    macs += block(14, 7, 1024, 512, True) + 2 * block(7, 7, 2048, 512, False)
    macs += 2048 * 1000
    assert 4.05e9 < macs < 4.15e9                   # the known ~4.1 GMAC
    assert mod.flops_per_item(sizes, "infer") == 2.0 * macs
    assert mod.flops_per_item(sizes, "train") == 6.0 * macs


def test_seq2seq_flops_against_its_matmul_shapes():
    mod, sizes = _config("seq2seq_attn")
    e = h = 512
    s = t = 30
    v = 30000
    enc = s * (e * 3 * h + h * 3 * h + h * h)
    dec = t * (s * h + s * h + e * 3 * h + h * 3 * h + h * 3 * h + h * v)
    per_token = (enc + dec + h * h) / (s + t)
    assert mod.flops_per_item(sizes, "train") == pytest.approx(
        6.0 * per_token)
    # the dictionary head is most of it
    assert t * h * v / (enc + dec) > 0.75


def test_order_statistics():
    assert stats.median([3, 1, 2]) == 2 and stats.median([4, 1, 3, 2]) == 2.5
    assert stats.percentile(list(range(100)), 0.99) == 99
    assert stats.percentile([], 0.5) is None
    assert stats.quartile_spread([10, 10, 10, 10]) == 0.0
    assert stats.quartile_spread([8, 9, 10, 11, 12]) == pytest.approx(0.2)
    before = {"counts": [1, 0, 0, 0], "boundaries": [1, 2, 4], "max": 9}
    after = {"counts": [1, 8, 1, 1], "boundaries": [1, 2, 4], "max": 9}
    assert stats.histogram_delta_quantile(before, after, 0.5) == 2.0
    assert stats.histogram_delta_quantile(before, after, 0.99) == 9.0
    assert stats.histogram_delta_quantile(before, before, 0.5) is None


def test_schedule_is_a_pure_function_of_the_seed():
    a = open_loop.poisson_schedule(11, 200.0, 2.0)
    b = open_loop.poisson_schedule(11, 200.0, 2.0)
    c = open_loop.poisson_schedule(12, 200.0, 2.0)
    assert np.array_equal(a, b) and not np.array_equal(a[:50], c[:50])
    assert np.all(np.diff(a) > 0) and a[0] > 0 and a[-1] < 2.0
    assert 300 < len(a) < 500                       # ~ rate * seconds


class _Handle:
    outputs, error = ["answer"], None

    def add_done_callback(self, cb):
        cb(self)


def test_a_stall_is_charged_to_the_requests_behind_it():
    """Request 2's submit blocks 150 ms (a stalled admission); requests due
    during the stall are sent late, and their latency, which runs from when
    they were DUE, carries the stall although each is answered at once."""
    schedule = np.arange(10) * 0.02                 # one every 20 ms

    def submit(i):
        if i == 2:
            time.sleep(0.15)
        return _Handle()

    records = open_loop.run(schedule, submit)
    assert [r.index for r in records] == list(range(10))
    assert all(r.error is None and r.outputs == ["answer"] for r in records)
    assert records[1].latency_s < 0.05
    assert records[2].latency_s >= 0.15
    assert records[3].latency_s >= 0.12             # due at 60 ms, sent ~190
    assert records[3].late_s >= 0.12
    assert records[9].latency_s < 0.05              # the backlog is gone


def test_a_rejection_is_a_result_and_the_sender_does_not_wait():
    released = threading.Event()

    class Slow:
        def add_done_callback(self, cb):
            threading.Thread(
                target=lambda: (released.wait(5), cb(self)),
                name="cb-test-answer", daemon=True).start()
        outputs, error = ["late"], None

    def submit(i):
        if i == 1:
            raise OverflowError("shed")
        return Slow()

    t0 = time.perf_counter()
    threading.Timer(0.2, released.set).start()
    records = open_loop.run(np.array([0.0, 0.01, 0.02]), submit)
    assert time.perf_counter() - t0 < 2.0
    assert records[1].error == "OverflowError" and records[1].done is not None
    assert records[0].error is None and records[0].latency_s >= 0.15
    assert all(r.sent < 0.1 for r in records)       # nobody waited to send

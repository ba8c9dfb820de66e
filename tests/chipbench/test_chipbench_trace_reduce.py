"""The trace -> metrics reduction, on a hand-made trace whose answers can
be worked out on paper and on a trace recorded on the chip."""
import gzip
import json
import os

import pytest

from conftest import BENCH

from chipbench.lib import trace_reduce as tr


def _trace(device_events, host_events=(), second_device=None):
    planes = [{"name": "/device:TPU:0",
               "lines": [{"name": "XLA Ops", "events": device_events},
                         {"name": "XLA Modules",
                          "events": [["jit_multi", 0, 10_000]]}]}]
    if second_device is not None:
        planes.append({"name": "/device:TPU:1", "lines": [
            {"name": "XLA Ops", "events": second_device}]})
    planes.append({"name": "/host:CPU", "lines": [
        {"name": "python3", "events": list(host_events)}]})
    return {"planes": planes}


def test_interval_arithmetic():
    assert tr.union([(5, 7), (0, 2), (1, 3), (7, 8), (9, 9)]) == \
        [(0, 3), (5, 8)]
    assert tr.total([(0, 3), (5, 8)]) == 6
    assert tr.subtract([(0, 10)], [(2, 3), (5, 7)]) == \
        [(0, 2), (3, 5), (7, 10)]
    assert tr.subtract([(0, 4), (6, 9)], [(3, 7)]) == [(0, 3), (7, 9)]
    assert tr.subtract([(0, 4)], []) == [(0, 4)]


def test_containers_are_not_work():
    """A while operation's event spans its body's events: only the
    innermost events count, or the device would look busy throughout."""
    events = [["while.1", 0, 1000], ["fusion.1", 100, 500],
              ["fusion.2", 600, 300], ["copy.3", 2000, 50]]
    assert sorted(e[0] for e in tr.work_events(events)) == \
        ["copy.3", "fusion.1", "fusion.2"]
    nested = [["while.1", 0, 1000], ["call.2", 100, 800],
              ["fusion.3", 150, 700]]
    assert [e[0] for e in tr.work_events(nested)] == ["fusion.3"]
    # a fusion that contains a copy's issue (a few ns) is still work
    issue = [["fusion.1", 0, 1000], ["copy-start.2", 10, 5]]
    assert sorted(e[0] for e in tr.work_events(issue)) == \
        ["copy-start.2", "fusion.1"]
    assert tr.short_name("%fusion.12 = f32[8]{0} fusion(f32[8]{0} %p)") == \
        "fusion.12"
    assert tr.short_name("all-reduce.7") == "all-reduce.7"


def test_busy_idle_collectives_and_gaps_on_overlapping_intervals():
    device = [
        ["%while.9 = (s32[]) while(...)", 1000, 9000],  # the container
        ["fusion.1", 1000, 2000],           # 1000..3000
        ["%all-reduce.2 = f32[8]{0} all-reduce(f32[8]{0} %x)", 2500, 2000],
        ["fusion.3", 4000, 1000],           # 4000..5000, hides 4000..4500
        ["fusion.1", 7000, 1000],           # 7000..8000 (same op again)
        ["all-gather.4", 9000, 1000],       # 9000..10000, fully exposed
    ]
    host = [["cb:window", 0, 6000], ["pt:run_steps:abc", 500, 5000],
            ["cb:window", 6000, 5000], ["$python frame", 0, 11000]]
    s = tr.summarize(_trace(device, host), n_devices=1, steps=4)
    assert s.window_s == pytest.approx(11000e-9)    # first to last cb:window
    # busy = [1000,5000] + [7000,8000] + [9000,10000] = 6000
    assert s.busy_s == pytest.approx(6000e-9)
    assert s.collective_s == pytest.approx(3000e-9)
    # exposed: 3000..4000 of the all-reduce, and the whole all-gather
    assert s.collective_exposed_s == pytest.approx(2000e-9)
    assert s.ops["fusion.1"] == pytest.approx(3000e-9)
    assert s.top_ops(2)[0][0] == "fusion.1" and len(s.top_ops(2)) == 2
    assert "while.9" not in s.ops
    # idle: 0..1000 under pt:run_steps? no: it starts at 500 -> the middle
    # (500) is inside it; 5000..7000 (middle 6000) under the 2nd cb:window;
    # 8000..9000 under the 2nd cb:window; 10000..11000 likewise
    gaps = dict(s.gaps)
    assert gaps["pt:run_steps:abc"] == pytest.approx(1000e-9)
    assert gaps["cb:window"] == pytest.approx(4000e-9)
    assert sum(gaps.values()) == pytest.approx(s.window_s - s.busy_s)
    assert s.steps == 4 and s.devices == 1


def test_busy_is_averaged_over_the_chips_and_clipped_to_the_window():
    one = [["fusion.1", 0, 4000]]
    two = [["fusion.1", 1000, 1000], ["fusion.2", 9000, 5000]]
    host = [["cb:window", 0, 10000]]
    s = tr.summarize(_trace(one, host, second_device=two), n_devices=2)
    # chip 0: 4000; chip 1: 1000 + (9000..10000 of fusion.2) = 2000
    assert s.busy_s == pytest.approx(3000e-9)
    assert s.devices == 2
    # only the first chip is asked for
    assert tr.summarize(_trace(one, host, second_device=two),
                        n_devices=1).busy_s == pytest.approx(4000e-9)


def test_without_window_spans_the_window_is_the_device_extent():
    s = tr.summarize(_trace([["fusion.1", 100, 100], ["fusion.2", 400, 100]]),
                     n_devices=1)
    assert s.window_s == pytest.approx(400e-9)
    assert s.busy_s == pytest.approx(200e-9)
    assert dict(s.gaps) == {"between spans": pytest.approx(200e-9)}


def test_an_empty_trace_has_no_busy_time():
    s = tr.summarize({"planes": [{"name": "/host:CPU", "lines": []}]},
                     n_devices=1)
    assert s.busy_s == 0.0 and s.window_s == 0.0


def _fixture(name):
    with gzip.open(os.path.join(BENCH, "fixtures", name), "rt") as fh:
        return json.load(fh)


def test_recorded_one_chip_trace_gives_fixed_numbers():
    """ResNet-50 bs256 run_steps(11) on the v5e: the end of one window, the
    3.8 ms the host takes to read the losses and dispatch again, and the
    start of the next window."""
    s = tr.summarize(_fixture("trace_resnet50_scan_v5e.json.gz"), n_devices=1)
    assert s.devices == 1 and len(s.ops) == 683
    assert s.window_s == pytest.approx(0.006792951, rel=1e-9)
    assert s.busy_s == pytest.approx(0.002984305, rel=1e-9)
    assert 100 * (1 - s.busy_s / s.window_s) == pytest.approx(56.068, abs=1e-3)
    assert s.top_ops(2) == [
        ["multiply_subtract_fusion.365", pytest.approx(0.000938274)],
        ["reshape.1181", pytest.approx(0.000337423)]]
    assert "while.5" not in s.ops                   # the scan's container
    assert s.collective_s == 0.0 and s.collective_exposed_s == 0.0
    gaps = dict(s.gaps)
    assert gaps["cb:window"] == pytest.approx(0.003807699, rel=1e-6)
    assert gaps["pt:run_steps:07485a07af06"] == pytest.approx(9.47e-7)


def test_recorded_four_chip_trace_gives_fixed_collective_numbers():
    """ResNet-50 dp=4 on four v5e chips, around one step's gradient
    all-reduce (devices 0 and 1 of the four).  libtpu runs the all-reduce
    as a synchronous operation of the ``XLA Ops`` line: while it runs
    nothing else does on that device, so all of it is exposed."""
    trace = _fixture("trace_resnet50_dp4_v5e.json.gz")
    one = tr.summarize(trace, n_devices=1)
    two = tr.summarize(trace, n_devices=2)
    assert (one.devices, two.devices) == (1, 2)
    assert one.window_s == pytest.approx(0.001497793, rel=1e-9)
    assert one.busy_s == pytest.approx(0.001497505, rel=1e-9)
    assert two.busy_s == pytest.approx(0.0014975095, rel=1e-9)   # the mean
    for s in (one, two):             # collectives are read on one device
        assert s.collective_s == pytest.approx(0.000897793, rel=1e-9)
        assert s.collective_exposed_s == pytest.approx(0.000897793, rel=1e-9)
    assert one.top_ops(2) == [
        ["all-reduce.561", pytest.approx(0.000897793)],
        ["fusion.2599", pytest.approx(0.000299843)]]
    assert dict(one.gaps) == {"cb:window": pytest.approx(2.88e-7)}

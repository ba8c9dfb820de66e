"""The yardstick's arithmetic: peaks, FLOP counts, order statistics, the
open-loop generator, the seeded weights, and what is asked of a
configuration that is cut to size."""
import importlib.util
import math
import os
import threading
import time

import numpy as np
import pytest

from conftest import BENCH, ROOT, load_json

from chipbench.lib import open_loop, peaks, stats, weights
from chipbench.lib.contract import reduced_problems


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


# --- a configuration cut to size (lib/contract.py) -------------------------

def _cut(entry_keys, cuts, **file_keys):
    """(entry, file) as BENCHMARK.json and configs/<name>.json would hold
    them: the entry names the cut keys, the file says from what to what."""
    data = {"name": "m", "reduced": list(cuts),
            "deployment": "one chip of one: layer 0 of 16 with all 64 "
                          "experts, the whole embedding and head",
            "hidden_size": 2048, "num_hidden_layers": 1, "num_experts": 16,
            "vocab_size": 12576}
    data.update(file_keys)
    return {"name": "m", "reduced": list(entry_keys)}, data


DEPTH = "num_hidden_layers: 16 -> 1"


def _existing(name):
    entry = [c for c in load_json(ROOT, "BENCHMARK.json")["configs"]
             if c["name"] == name][0]
    return entry, load_json(BENCH, "configs", f"{name}.json")


@pytest.mark.parametrize("entry,data", [
    pytest.param(*_existing("resnet50"), id="resnet50-uncut"),
    pytest.param(*_existing("seq2seq_attn"), id="seq2seq_attn-uncut"),
    pytest.param(*_cut(["num_hidden_layers"], [DEPTH]), id="depth-cut"),
    pytest.param(*_cut(["num_experts", "vocab_size"],
                       ["num_experts: 64 -> 16",
                        "vocab_size: 50304 -> 12576"]), id="two-keys"),
])
def test_a_configuration_is_admitted(entry, data):
    assert reduced_problems(entry, data) == []


def _no_deployment():
    entry, data = _cut(["num_hidden_layers"], [DEPTH])
    del data["deployment"]
    return entry, data


@pytest.mark.parametrize("entry,data,names", [
    pytest.param(*_cut([], [DEPTH]), "num_hidden_layers",
                 id="entry-says-uncut"),
    pytest.param(*_cut([DEPTH], [DEPTH]), DEPTH,
                 id="entry-holds-the-string-not-the-key"),
    pytest.param(*_cut(["num_hidden_layers"], ["num_hidden_layers 16 -> 1"]),
                 "num_hidden_layers 16 -> 1", id="no-colon"),
    pytest.param(*_cut(["num_hidden_layers"], ["num_hidden_layers: 16 to 1"]),
                 "num_hidden_layers: 16 to 1", id="no-arrow"),
    pytest.param(*_cut(["num_hidden_layers"],
                       ["num_hidden_layers: sixteen -> 1"]),
                 "sixteen", id="not-json"),
    pytest.param(*_cut(["n_layer"], ["n_layer: 16 -> 1"]), "n_layer",
                 id="key-absent"),
    pytest.param(*_cut(["num_hidden_layers"], [DEPTH], num_hidden_layers=2),
                 "num_hidden_layers = 2", id="file-runs-another-value"),
    pytest.param(*_cut(["num_hidden_layers"], [DEPTH], num_hidden_layers=1.0),
                 "num_hidden_layers = 1.0", id="one-is-the-integer"),
    pytest.param(*_cut(["num_hidden_layers"], ["num_hidden_layers: 1 -> 1"]),
                 "not changed", id="published-is-run"),
    pytest.param(*_cut(["num_experts", "num_experts"],
                       ["num_experts: 64 -> 16", "num_experts: 32 -> 16"]),
                 "num_experts is named by more than one", id="key-twice"),
    pytest.param(*_no_deployment(), "deployment", id="deployment-missing"),
    pytest.param(*_cut(["num_hidden_layers"], [DEPTH], deployment="  "),
                 "deployment", id="deployment-empty"),
    pytest.param(*_cut(["num_hidden_layers"], [DEPTH], deployment="x" * 301),
                 "deployment", id="deployment-301-characters"),
    pytest.param(*_cut([f"k{i}" for i in range(9)],
                       [f"k{i}: 2 -> 1" for i in range(9)],
                       **{f"k{i}": 1 for i in range(9)}),
                 "at most 8", id="nine-strings"),
    pytest.param(*_cut(["num_hidden_layers"], [DEPTH + " " * (101 - len(DEPTH))]),
                 "at most 100", id="101-characters"),
])
def test_a_configuration_is_refused_with_what_is_at_fault(entry, data, names):
    problems = reduced_problems(entry, data)
    assert problems and any(names in p for p in problems), problems


# --- weights from the seed (lib/weights.py) --------------------------------

@pytest.mark.parametrize("shape,fans", [
    ((512, 30000), 512 + 30000),                 # seq2seq's head [in, out]
    ((64, 256, 1, 1), 256 + 64),                 # a 1x1 filter [out, in, 1, 1]
    ((64, 3, 7, 7), 3 * 49 + 64 * 49),           # ResNet's stem
    ((64, 2048, 1024), 2048 + 1024),             # 64 experts, each [in, out]
    ((64, 1024, 2048), 1024 + 2048),
    ((5, 7, 9), 7 + 9),                          # bilinear [size, dx, dy]
    ((8, 6, 3, 3, 3), 6 * 27 + 8 * 27),          # a 3-d filter [out, in, k...]
])
def test_xavier_limit_by_rank(shape, fans):
    """Pinned to hand values: a constant of an existing cell's jitted draw
    that moved would be another program, and other weights."""
    assert weights._xavier_limit(shape) == math.sqrt(6.0 / fans)


@pytest.fixture(scope="module")
def seeded():
    """A program with a filter, a matrix and two stacks of expert matrices:
    its parameters' shapes, its seeder, and the seeder's draw for seed 3."""
    import paddle_tpu as pt
    from paddle_tpu import layers

    pt.core.reset_default_programs()
    pt.unique_name.reset()
    img = layers.data("img", shape=[3, 8, 8], dtype="float32")
    conv = layers.conv2d(img, num_filters=4, filter_size=3)
    hidden = layers.fc(conv, size=6)
    layers.moe(hidden, num_experts=4, expert_hidden=5)
    main = pt.default_main_program()
    shapes = {p.name: tuple(p.shape)
              for p in main.global_block().all_parameters()}
    draw = weights.seeder(main)
    try:
        yield shapes, draw, {k: np.asarray(v) for k, v in draw(3).items()}
    finally:
        pt.core.reset_default_programs()
        pt.unique_name.reset()


def _of_shape(drawn, shape):
    return [v for v in drawn.values() if v.shape == shape]


def test_seeder_draws_weights_and_leaves_biases(seeded):
    shapes, _, drawn = seeded
    assert sorted(v.shape for v in drawn.values()) == sorted(
        [(4, 3, 3, 3), (144, 6), (6, 4), (4, 6, 5), (4, 5, 6)])
    left = sorted(shape for name, shape in shapes.items()
                  if name not in drawn)
    assert left and all(len(shape) == 1 for shape in left), left
    for name, value in drawn.items():
        assert value.dtype == np.float32 and value.shape == shapes[name]


def test_seeder_keeps_every_weight_inside_its_limit(seeded):
    _, _, drawn = seeded
    for name, value in drawn.items():
        limit = weights._xavier_limit(value.shape)
        assert 0.5 * limit < np.abs(value).max() <= limit, (name, limit)
    (router,) = _of_shape(drawn, (6, 4))
    assert np.abs(router).max() <= math.sqrt(6.0 / (6 + 4))


@pytest.mark.parametrize("shape", [(4, 6, 5), (4, 5, 6)])
def test_seeder_scales_an_expert_stack_as_matrices(seeded, shape):
    """Each expert is a [6, 5] (or [5, 6]) matrix: its 120 values reach
    for sqrt(6 / 11) = 0.739; read as a filter [out, in, k] the limit would
    be sqrt(6 / 50) = 0.346, every expert product 2x too small."""
    (stack,) = _of_shape(seeded[2], shape)
    assert stack.size == 120
    assert np.abs(stack).max() > 0.9 * math.sqrt(6.0 / 11) > 0.346


def test_seeder_is_a_pure_function_of_the_seed(seeded):
    _, draw, drawn = seeded
    again, other = draw(3), draw(2 ** 31 + 5)
    for name, value in drawn.items():
        assert np.array_equal(value, np.asarray(again[name])), name
        assert not np.array_equal(value, np.asarray(other[name])), name

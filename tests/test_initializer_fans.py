"""The startup program draws a stack of matrices matrix by matrix: the
expert stacks of ``layers.moe`` and ``bilinear_tensor_product``'s weight
state the fans of ONE matrix, so their Xavier limit is the one the
benchmark's seeder (chipbench/lib/weights.py) gives them; a filter keeps
its receptive-field fans."""
import math

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import layers


def _uniform_limits(program):
    """{parameter name: the limit of the uniform_random op that draws it}."""
    return {op.output_names[0]: op.attrs["max"]
            for op in program.global_block().ops
            if op.type == "uniform_random"}


def test_a_stack_of_matrices_is_drawn_as_matrices_and_a_filter_as_a_filter():
    x = layers.data("x", shape=[16, 2048], dtype="float32")
    layers.moe(x, num_experts=64, expert_hidden=1024, top_k=8,
               capacity_factor=None, act="silu", gated=True,
               param_attr=pt.ParamAttr(name="experts"))
    a = layers.data("a", shape=[6], dtype="float32")
    b = layers.data("b", shape=[10], dtype="float32")
    layers.bilinear_tensor_product(a, b, size=4,
                                   param_attr=pt.ParamAttr(name="bilinear"))
    img = layers.data("img", shape=[3, 8, 8], dtype="float32")
    layers.conv2d(img, num_filters=5, filter_size=3, bias_attr=False,
                  param_attr=pt.ParamAttr(name="filter"))
    limits = _uniform_limits(pt.default_startup_program())
    stack = math.sqrt(6.0 / (2048 + 1024))
    # 0.0442, not the 0.00167 of a [64, 2048, 1024] read as a filter
    assert stack == pytest.approx(0.0442, abs=5e-5)
    assert math.sqrt(6.0 / (2048 * 1024 + 64 * 1024)) == pytest.approx(
        0.00167, abs=5e-6)
    for name in ("experts_up", "experts_gate", "experts_down"):
        assert limits[name] == pytest.approx(stack), name
    assert limits["bilinear"] == pytest.approx(math.sqrt(6.0 / (6 + 10)))
    assert limits["filter"] == pytest.approx(
        math.sqrt(6.0 / (3 * 9 + 5 * 9)))
    # the benchmark's seeder agrees on the stacks
    from chipbench.lib.weights import _xavier_limit
    assert _xavier_limit((64, 2048, 1024)) == pytest.approx(stack)
    assert np.isclose(_xavier_limit((4, 6, 10)), limits["bilinear"])

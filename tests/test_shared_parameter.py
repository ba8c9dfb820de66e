"""One named parameter read by several ops: a ``ParamAttr`` name that
stands returns the parameter that stands and draws it once."""
import os
import sys

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import layers

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _two_readers():
    x = layers.data("x", shape=[4], dtype="float32")
    shared = pt.ParamAttr(name="shared.w")
    a = layers.fc(x, size=4, param_attr=shared, bias_attr=False)
    first = pt.default_main_program().global_block().var("shared.w")
    b = layers.fc(a, size=4, param_attr=pt.ParamAttr(name="shared.w"),
                  bias_attr=False)
    return x, first, layers.mean(b)


def test_a_name_that_stands_is_one_parameter_and_one_initializer():
    _, first, _ = _two_readers()
    main, startup = pt.default_main_program(), pt.default_startup_program()
    # the SAME object under both ops, not a second one under the first's name
    assert main.global_block().var("shared.w") is first
    assert [p.name for p in main.all_parameters()] == ["shared.w"]
    muls = [op for op in main.global_block().ops if op.type == "mul"]
    assert [op.input("Y") for op in muls] == [["shared.w"], ["shared.w"]]
    writers = [op for op in startup.global_block().ops
               if "shared.w" in op.output_names]
    assert len(writers) == 1


def test_the_gradients_of_a_shared_parameter_sum_over_its_readers():
    import jax
    import jax.numpy as jnp

    _, _, loss = _two_readers()
    pt.optimizer.SGD(0.0).minimize(loss)
    exe = pt.Executor()
    exe.run(pt.default_startup_program(), feed={}, fetch_list=[])
    rng = np.random.RandomState(0)
    w = rng.standard_normal((4, 4)).astype(np.float32)
    pt.global_scope().set("shared.w", w)
    feed = {"x": rng.standard_normal((3, 4)).astype(np.float32)}
    got = exe.run(feed=feed, fetch_list=[loss, "shared.w@GRAD"])
    want = jax.value_and_grad(
        lambda w: jnp.mean(feed["x"] @ w @ w))(jnp.asarray(w))
    np.testing.assert_allclose(got[0], want[0], rtol=1e-6)
    np.testing.assert_allclose(got[1], want[1], rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("size,dtype,what", [(5, "float32", "shape"),
                                             (4, "float64", "dtype")])
def test_sharing_under_another_shape_or_dtype_is_refused(size, dtype, what):
    x = layers.data("x", shape=[4], dtype="float32")
    layers.fc(x, size=4, param_attr=pt.ParamAttr(name="shared.w"),
              bias_attr=False)
    helper = pt.layer_helper.LayerHelper("fc")
    with pytest.raises(ValueError, match="shared.w.*cannot be shared"):
        helper.create_parameter(pt.ParamAttr(name="shared.w"),
                                shape=[4, size], dtype=dtype)
    assert len(pt.default_startup_program().global_block().ops) == 1


def test_every_listing_of_the_state_holds_a_shared_parameter_once():
    """``program.all_parameters()``, the benchmark's seeded draw and the
    state ``run_steps`` donates: a looped model's head, read by every pass,
    is in each of them once, and trains."""
    from paddle_tpu import models

    sys.path.insert(0, ROOT)
    from chipbench.lib import weights

    ids = layers.data("ids", shape=[8], dtype="int64")
    lbl = layers.data("lbl", shape=[8], dtype="int64")
    loss, _ = models.ouro_loss(ids, lbl, 32, hidden_size=16, num_layers=1,
                               num_heads=2, ffn_size=24, total_ut_steps=3)
    pt.optimizer.Adam(1e-2).minimize(loss)
    main, startup = pt.default_main_program(), pt.default_startup_program()
    names = [p.name for p in main.all_parameters()]
    assert len(names) == len(set(names)) == 11 + 5
    readers = [op for b in main.blocks for op in b.ops
               if op.type == "mul" and op.input("Y") == ["ouro.head"]]
    assert len(readers) == 3
    drawn = [n for op in startup.global_block().ops for n in op.output_names]
    assert drawn.count("ouro.head") == 1 and drawn.count("ouro.l0.wq") == 1
    draw = weights.seeder(main)(5)
    assert sorted(draw) == sorted(n for n in names if "norm" not in n
                                  and "exit_gate" not in n)
    exe = pt.Executor()
    exe.run(startup, feed={}, fetch_list=[])
    weights.reseed(pt.global_scope(), weights.seeder(main), 5)
    feed = {"ids": np.arange(16).reshape(2, 8) % 32,
            "lbl": np.arange(16).reshape(2, 8)[:, ::-1] % 32}
    entry = exe.compile(main, feed=feed, fetch_list=[loss], num_steps=4)
    assert entry is not None
    state = exe._state_keys(main, pt.global_scope())
    assert len(state) == len(set(state))
    assert state.count("ouro.head") == 1
    (losses,) = exe.run_steps(4, main, feed=feed, fetch_list=[loss])
    assert np.all(np.isfinite(losses)) and losses[-1] < losses[0]

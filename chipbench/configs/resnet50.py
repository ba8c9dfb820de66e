"""ResNet-50 for ImageNet: the program under test (through the public
layers API) and its plain float32 reference.

``reference`` follows He et al. 2015, table 1 (bottleneck blocks 3-4-6-3,
stride on the 3x3 convolution as the program and the reference framework's
resnet.py place it, projection shortcuts where shape changes), batch
normalization with batch statistics when training and running statistics
when serving, and the textbook loss -log softmax(logits)[label].  It shares
no code with ``paddle_tpu``: only parameter NAMES, which are the layer
helper's creation order (conv2d_<i>.w_0, batch_norm_<i>.{w_0,b_0,global_0,
global_1}, fc_0.{w_0,b_0}).
"""
from __future__ import annotations

BN_EPS = 1e-5

# What the training step is held to (relative errors: |loss - ref| / |ref|,
# ||g - ref||_2 / ||ref||_2).
#
# With BATCH statistics this network is chaotic at a seeded start: a 1e-6
# relative change of the input moves the float64 reference's own gradient
# of the first and of the middle convolution by 2.8 % (batch normalization's
# backward pass amplifies layer by layer, PERF.md section 6), and
# bfloat16's 2^-9 roundings leave those gradients 130 % from the float32
# reference on the chip, on any correct implementation.  What is before
# that amplification is held: the LOSS of the window's first step, on the
# whole window batch at the seeded weights (``WINDOW_LOSS_REL_TOL``;
# measured 0.07 % to 0.25 % over 11 runs, of which up to 0.2 % is the
# program's rounding of its loss to bfloat16).
#
# With the statistics FROZEN (``is_test``: the same program normalizes by
# its stored mean 0 and variance 1) the same 53 convolutions, pooling,
# classifier, loss and whole backward pass are well-conditioned, and the
# loss and all three gradients are held on a seeded sample batch: this is
# the check that a wrong convolution gradient fails.  Measured on the chip
# (PR 22, 18 runs on one and four chips, each with its own seed): loss
# 0.01 % to 0.25 %; first convolution 16 % to 20 % (the longest backward
# chain, through the max-pool's scatter); middle convolution 3.0 % to
# 3.8 %; classifier 0.38 % to 0.59 %.
#
# Each bound is 2.5 to 4 times the largest error measured, so another seed
# passes, while an 8-bit format (sixteen times bfloat16's rounding) or a
# dropped term does not.
WINDOW_LOSS_REL_TOL = 1e-2
CHECKS = (
    {"name": "frozen_stats", "is_test": True, "loss_rel_tol": 1e-2,
     "grad_rel_tol": {"conv2d_0.w_0": 0.5, "conv2d_26.w_0": 0.12,
                      "fc_0.w_0": 0.02}},
)

# Serving: max |log p - ref| over a sample's log-probabilities, bfloat16
# inference through 50 layers against the float32 reference.  Measured on
# the chip (PR 22, two seeds): 0.0071 / 0.0075; the bound is three times
# that.
INFER_LOGP_TOL = 0.025


def _plan(sizes):
    """[(out_channels, kernel, stride, pad, relu)] in creation order, with
    block boundaries: ('conv', ...), ('block_in',), ('block_out', has_proj)."""
    plan = [("conv", sizes["stem_width"], 7, 2, 3, True), ("maxpool",)]
    ch_in = sizes["stem_width"]
    exp = sizes["bottleneck_expansion"]
    for stage, (width, n) in enumerate(zip(sizes["stage_widths"],
                                           sizes["stage_blocks"])):
        for i in range(n):
            stride = 2 if i == 0 and stage > 0 else 1
            proj = ch_in != width * exp or stride != 1
            plan.append(("block_in",))
            plan.append(("conv", width, 1, 1, 0, True))
            plan.append(("conv", width, 3, stride, 1, True))
            plan.append(("conv", width * exp, 1, 1, 0, False))
            plan.append(("block_out", width * exp, stride, proj))
            ch_in = width * exp
    return plan


def build(mode, batch, sizes):
    """The program: ``mode`` 'train' (loss + Momentum) or 'infer'."""
    import paddle_tpu as pt
    from paddle_tpu import layers, models

    if sizes["depth"] != 50:
        raise ValueError("resnet50: depth is 50")
    pt.core.reset_default_programs()
    pt.core.reset_global_scope()
    pt.unique_name.reset()
    s, c = sizes["image_size"], sizes["image_channels"]
    img = layers.data("img", shape=[c, s, s], dtype="float32")
    pred = models.resnet50(img, num_classes=sizes["num_classes"])
    feeds = {"img": {"shape": [c, s, s], "dtype": "float32"}}
    built = {"main": pt.default_main_program(),
             "startup": pt.default_startup_program(),
             "feeds": feeds, "output": pred.name, "amp": sizes["compute_dtype"] == "bfloat16",
             "items_per_example": 1}
    if mode == "train":
        label = layers.data("label", shape=[1], dtype="int64")
        loss = layers.mean(layers.cross_entropy(pred, label))
        opt = sizes["optimizer"]
        pt.optimizer.Momentum(learning_rate=opt["learning_rate"],
                              momentum=opt["momentum"]).minimize(loss)
        feeds["label"] = {"shape": [1], "dtype": "int64",
                          "high": sizes["num_classes"]}
        built["loss"] = loss.name
    return built


def flops_per_item(sizes, mode):
    """FLOPs the mathematics needs per image: 2 per multiply-accumulate of
    every convolution and of the classifier; a training step is forward +
    two backward products = 3x.  Normalization, pooling and the optimizer
    are not counted (model FLOPs, not hardware FLOPs)."""
    hw = sizes["image_size"]
    ch_in = sizes["image_channels"]
    macs = 0
    block_in = None
    for step in _plan(sizes):
        if step[0] == "conv":
            _, out, k, stride, pad, _ = step
            hw = (hw + 2 * pad - k) // stride + 1
            macs += hw * hw * out * ch_in * k * k
            ch_in = out
        elif step[0] == "maxpool":
            hw = (hw + 2 - 3) // 2 + 1
        elif step[0] == "block_in":
            block_in = (ch_in, hw)
        elif step[0] == "block_out":
            _, out, stride, proj = step
            if proj:
                macs += hw * hw * out * block_in[0]
    macs += ch_in * sizes["num_classes"]
    return 2.0 * macs * (3 if mode == "train" else 1)


def _forward(params, img, sizes, training):
    import jax.numpy as jnp
    from jax import lax

    hi = lax.Precision.HIGHEST
    n = {"conv": 0}

    def conv_bn(x, k, stride, pad, relu):
        i = n["conv"]
        n["conv"] += 1
        y = lax.conv_general_dilated(
            x, params[f"conv2d_{i}.w_0"], (stride, stride),
            [(pad, pad), (pad, pad)],
            dimension_numbers=("NCHW", "OIHW", "NCHW"), precision=hi)
        if training:
            mean = jnp.mean(y, axis=(0, 2, 3))
            var = jnp.var(y, axis=(0, 2, 3))
        else:
            mean = params[f"batch_norm_{i}.global_0"]
            var = params[f"batch_norm_{i}.global_1"]
        y = (y - mean[None, :, None, None]) \
            / jnp.sqrt(var + BN_EPS)[None, :, None, None]
        y = y * params[f"batch_norm_{i}.w_0"][None, :, None, None] \
            + params[f"batch_norm_{i}.b_0"][None, :, None, None]
        return jnp.maximum(y, 0.0) if relu else y

    x = img
    block_in = None
    for step in _plan(sizes):
        if step[0] == "conv":
            _, _, k, stride, pad, relu = step
            x = conv_bn(x, k, stride, pad, relu)
        elif step[0] == "maxpool":
            x = lax.reduce_window(x, -jnp.inf, lax.max, (1, 1, 3, 3),
                                  (1, 1, 2, 2),
                                  ((0, 0), (0, 0), (1, 1), (1, 1)))
        elif step[0] == "block_in":
            block_in = x
        elif step[0] == "block_out":
            _, _, stride, proj = step
            short = conv_bn(block_in, 1, stride, 0, False) if proj \
                else block_in
            x = jnp.maximum(short + x, 0.0)
    pooled = jnp.mean(x, axis=(2, 3))
    return jnp.dot(pooled, params["fc_0.w_0"], precision=hi) \
        + params["fc_0.b_0"]


def reference(mode, params, feeds, sizes, frozen_stats=False):
    """'infer': log-probabilities [B, classes] with the stored statistics.
    'loss': the training loss with batch statistics, forward only.
    'train': (loss, {name: gradient}) for ``sizes['check_params']``, with
    batch statistics or, ``frozen_stats``, the stored ones.  float32
    throughout, matmul precision 'highest'."""
    import jax
    import jax.numpy as jnp

    params = {k: jnp.asarray(v, jnp.float32) for k, v in params.items()}
    img = jnp.asarray(feeds["img"], jnp.float32)

    def loss_fn(wrt, rest, x, y, training):
        logp = jax.nn.log_softmax(
            _forward({**rest, **wrt}, x, sizes, training))
        return -jnp.mean(jnp.take_along_axis(logp, y[:, None], axis=1))

    with jax.default_matmul_precision("highest"):
        if mode == "infer":
            return jax.jit(lambda p, x: jax.nn.log_softmax(
                _forward(p, x, sizes, training=False)))(params, img)
        label = jnp.asarray(feeds["label"]).reshape(-1)
        if mode == "loss":
            return jax.jit(lambda p, x, y: loss_fn({}, p, x, y, True))(
                params, img, label)
        wrt = {k: params[k] for k in sizes["check_params"]}
        rest = {k: v for k, v in params.items() if k not in wrt}
        return jax.jit(jax.value_and_grad(
            lambda wrt, rest, x, y: loss_fn(wrt, rest, x, y,
                                            not frozen_stats)))(
            wrt, rest, img, label)

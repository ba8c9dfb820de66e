"""Kernels of the main path compiled for a described (not attached) TPU
v5e at real widths: what the chip's compiler refuses (a block off the
tiling, too much VMEM) fails here, at no chip time.  Nothing runs, so
nothing here is a result or a time.

The topology is described inside a fixture, never at import: only the
worker that runs this file loads the TPU's library.  Keep every such test
in THIS file."""
import functools
import os
import re
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from paddle_tpu.core import compile_cache
from paddle_tpu.ops import moe_ops, nn_ops, pallas_kernels


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                        # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


D, H, EXPERTS = 2048, 1024, 64               # OLMoE's experts
TILE = moe_ops.ROW_TILE
ROWS = (8 * 4096 // TILE + EXPERTS) * TILE    # 8 x 4096 routed rows, tiled


@pytest.fixture(scope="module")
def spec(one_chip):
    def spec(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    return spec


@pytest.fixture(scope="module")
def layout(spec):
    """``tile_group`` and ``num_tiles``."""
    return spec((ROWS // TILE,), jnp.int32), spec((1,), jnp.int32)


def _kernels(text):
    return text.count('custom_call_target="tpu_custom_call"')


def test_grouped_matmul_compiles_at_olmoe_widths(spec, layout):
    """The three kernels (product, product with the stack transposed,
    gradient of the stack) for 64 experts of [2048, 1024] and 8 x 4096
    routed rows in the lowering's tiles: Mosaic takes the blocks and the
    VMEM they need."""
    def loss(lhs, rhs, tile_group, num_tiles):
        out = pallas_kernels._grouped(lhs, rhs, tile_group, num_tiles, False)
        return jnp.sum(out * out)

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        spec((ROWS, D)), spec((EXPERTS, D, H)), *layout).compile()
    assert _kernels(compiled.as_text()) == 3
    # operands, results and the forward's output; nothing of E x rows
    assert compiled.memory_analysis().temp_size_in_bytes < 4 * ROWS * D * 3


@pytest.mark.parametrize("pair,kernels,row_results,row_sums", [
    (True, 7, 2, 0), (False, 9, 3, 1)])
def test_gated_experts_take_six_kernels_and_no_sum_of_row_gradients(
        spec, layout, pair, kernels, row_results, row_sums):
    """The experts stage of a gated ``moe`` op at OLMoE's widths, gradient
    and all: gate and up as the pair (``_gated``) are three products, two
    weight blocks each inside ``GMM_VMEM_BYTES``, and with ``down``'s three
    the program holds six where two ``grouped_matmul`` calls made nine; a
    seventh kernel is the derivative of ``act(gate) * up`` over the tiles
    in use, which XLA computes over all of them in the nine's program.  Of
    [rows, d] kernel results it holds ``down``'s output and ONE gradient of
    the rows, so no second one exists and nothing adds two of them."""
    def loss(lhs, w_gate, w_up, w_down, tile_group, num_tiles):
        def product(a, w):
            return pallas_kernels._grouped(a, w, tile_group, num_tiles, False)
        hidden = pallas_kernels._gated(
            lhs, w_gate, w_up, tile_group, num_tiles, jax.nn.silu, False
        ) if pair else jax.nn.silu(product(lhs, w_gate)) * product(lhs, w_up)
        return jnp.sum(product(hidden, w_down) ** 3)

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3))).lower(
        spec((ROWS, D)), spec((EXPERTS, D, H)), spec((EXPERTS, D, H)),
        spec((EXPERTS, H, D)), *layout).compile().as_text()
    wide = rf"= f32\[{ROWS},{D}\]\S* "
    assert (_kernels(text), len(re.findall(wide + r"custom-call\(", text)),
            len(re.findall(wide + r"add\(", text))
            ) == (kernels, row_results, row_sums)


@pytest.mark.parametrize("act", sorted(moe_ops._ACTS))
def test_gated_forward_takes_every_activation_in_its_epilogue(spec, layout,
                                                              act):
    """``act(gate) * up`` is the forward kernel's epilogue: Mosaic lowers
    each activation a ``moe`` op may name, at OLMoE's widths."""
    text = jax.jit(functools.partial(
        pallas_kernels._gated, act=moe_ops._ACTS[act], interpret=False)
    ).lower(spec((ROWS, D)), spec((EXPERTS, D, H)), spec((EXPERTS, D, H)),
            *layout).compile().as_text()
    assert _kernels(text) == 1


# rope at the two cells' shapes: [B, 4096 positions, 16 heads of 128]
T_LEN, HEADS, HEAD_DIM = 4096, 16, 128


def _rope_op(x, backend, scope="pt.rope:0.0"):
    """The ``rope`` lowering as a step traces it on ``backend``, under the
    scope the executor gives an op."""
    with pytest.MonkeyPatch.context() as patch, jax.named_scope(scope):
        patch.setattr(jax, "default_backend", lambda: backend)
        return nn_ops._rope(SimpleNamespace(mesh=None), {"X": [x]},
                            {"theta": 1e6})["Out"]


def _half_width_arrays(text):
    """Arrays whose minor dimension is D/2: the two halves of the rotation
    as XLA writes them to HBM, each padded to whole 128-lane tiles."""
    return len(re.findall(rf"f32\[\d+,\d+,\d+,{HEAD_DIM // 2}\]", text))


@pytest.mark.parametrize("batch", [1, 2])
def test_rope_is_one_kernel_a_direction_and_no_half_width_array(spec, batch):
    """Value and gradient of ``rope`` on the head-major view the kernel
    works on: two custom calls (the backward is the forward's kernel with
    the sine table negated), next to no temporaries, nothing of width D/2.
    The formula it replaces on the TPU, which is the reason the kernel
    exists: both halves written lane-padded, in both directions."""
    def loss(backend, x, mix):           # x, mix: [B, H, T, D]
        out = _rope_op(jnp.moveaxis(x, 1, 2), backend)
        return jnp.sum(jnp.moveaxis(out, 2, 1) * mix)

    head_major = spec((batch, HEADS, T_LEN, HEAD_DIM))
    kernel = jax.jit(jax.value_and_grad(functools.partial(loss, "tpu"))
                     ).lower(head_major, head_major).compile()
    assert _kernels(kernel.as_text()) == 2
    assert _half_width_arrays(kernel.as_text()) == 0
    assert kernel.memory_analysis().temp_size_in_bytes < 1 << 20

    formula = jax.jit(jax.value_and_grad(functools.partial(loss, "cpu"))
                      ).lower(head_major, head_major).compile()
    assert _kernels(formula.as_text()) == 0
    assert _half_width_arrays(formula.as_text()) >= 4
    # at least one array of X's size in temporaries a direction
    assert formula.memory_analysis().temp_size_in_bytes \
        >= 4 * batch * T_LEN * HEADS * HEAD_DIM


def test_rope_between_projection_and_attention_leaves_no_copy(spec):
    """Where Ouro has the op (a projection reshaped to heads in front,
    ``flash_attention`` behind; one sequence), gradient and all: the
    transposes to and from the kernel's head-major view are XLA's to
    assign, the projection writes that layout and the attention kernels
    read the result as it is, so the module holds the six kernels and no
    copy or transpose of an array of q's size in float32.  Each ``rope`` kernel
    carries its op's scope and direction in its ``op_name`` (the calls into
    the jitted ``_rope_call`` are inlined, the names joined): what the
    per-layer metrics of a traced run find it by."""
    batch, width = 1, HEADS * HEAD_DIM

    def loss(x, wq, wk, wv):
        def heads(w):
            return (x @ w).reshape(batch, T_LEN, HEADS, HEAD_DIM)
        q = _rope_op(heads(wq), "tpu", "pt.rope:0.3")
        k = _rope_op(heads(wk), "tpu", "pt.rope:0.5")
        out = pallas_kernels.flash_attention(
            q, k, heads(wv), causal=True, block_q=1024, block_k=1024,
            use_pallas=True)
        return jnp.sum(out ** 2)

    # (products as a step on the chip has them: under conftest's 'highest'
    # the attention kernels' multi-pass products overrun their VMEM)
    with jax.default_matmul_precision("default"):
        text = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3))).lower(
            spec((batch, T_LEN, width)), *[spec((width, width))] * 3
        ).compile().as_text()
    assert _kernels(text) == 2 * 2 + 2
    assert _half_width_arrays(text) == 0
    q_sized = rf"= f32\[{batch},(?:{T_LEN},{HEADS}|{HEADS},{T_LEN}),{HEAD_DIM}\]"
    assert re.findall(q_sized + r"\S* (?:copy|transpose)\(", text) == []
    assert sorted(re.findall(
        r'custom-call\(.*op_name="jit\(loss\)/([^"]*)/jit\(_rope_call\)'
        r'/pallas_call"', text)) == sorted(
        way.format(op) for op in ("pt.rope:0.3", "pt.rope:0.5")
        for way in ("jvp({})", "transpose(jvp({}))"))


# LFM2-8B-A1B's operators at the cell's shapes: 8192 positions of 2048
# features, 32 query heads over 8 key / value heads of 64
LFM2_T, LFM2_D = 8192, 2048


@pytest.mark.parametrize("batch", [1, 2])
def test_short_conv_is_one_kernel_a_direction(spec, batch):
    """The gated short convolution, value and gradient: one custom call a
    direction and next to no temporaries (the formula through XLA keeps v
    and four shifted products)."""
    def loss(x, w, mix):
        return jnp.sum(pallas_kernels.short_conv(x, w) * mix)

    compiled = jax.jit(jax.value_and_grad(loss, argnums=(0, 1))).lower(
        spec((batch, LFM2_T, 3 * LFM2_D)), spec((LFM2_D, 3)),
        spec((batch, LFM2_T, LFM2_D))).compile()
    assert _kernels(compiled.as_text()) == 2
    assert compiled.memory_analysis().temp_size_in_bytes \
        < 1.5 * batch * LFM2_T * LFM2_D * 4


def _attention_grads(spec, batch, seq, heads, kv_heads, d, with_value=False):
    """The compiled module text of ``flash_attention``'s gradients (and the
    value) from the [B, T, H, D] arrays a model hands the op, at the blocks
    the cells run (the layer's default 1024 x 1024)."""
    def loss(q, k, v, mix):
        return jnp.sum(pallas_kernels.flash_attention(
            q, k, v, causal=True, block_q=1024, block_k=1024,
            use_pallas=True) * mix)

    grad = jax.value_and_grad if with_value else jax.grad
    # (products as a step on the chip has them: under conftest's 'highest'
    # the one-pass backward's multi-pass products ask for 32.70 MB at two of
    # LFM2's sequences, 0.7 over what the kernel may take)
    with jax.default_matmul_precision("default"):
        return jax.jit(grad(loss, argnums=(0, 1, 2))).lower(
            spec((batch, seq, heads, d)), spec((batch, seq, kv_heads, d)),
            spec((batch, seq, kv_heads, d)),
            spec((batch, seq, heads, d))).compile().as_text()


@pytest.mark.parametrize("batch", [1, 2])
def test_grouped_query_attention_compiles_at_lfm2_heads(spec, batch):
    """32 query heads reading 8 K / V heads of 64 through the index maps:
    TWO kernels, the forward and the one-pass backward (three before PR 37,
    when dQ and dK / dV each recomputed the scores), dK and dV at the 8
    heads they have, and no K or V repeated to 32 heads anywhere in the
    module.  (Four [1024, 1024] float32 temporaries are the default 16 MiB
    of scoped VMEM; every backward call asks for
    ``FLASH_BWD_VMEM_BYTES``.)"""
    heads, kv_heads, d = 32, 8, 64
    before = dict(compile_cache.stats().snapshot())
    text = _attention_grads(spec, batch, LFM2_T, heads, kv_heads, d)
    assert compile_cache.stats().snapshot().get(
        "route/flash_attention_bwd:one_pass", 0) - before.get(
        "route/flash_attention_bwd:one_pass", 0) == 1
    assert _kernels(text) == 2
    results = re.findall(
        rf"\(f32\[{batch * heads},{LFM2_T},{d}\]\S*, "
        rf"f32\[{batch * kv_heads},{LFM2_T},{d}\]\S*, "
        rf"f32\[{batch * kv_heads},{LFM2_T},{d}\]\S*\) custom-call\(", text)
    assert len(results) == 1
    assert not re.search(rf"f32\[{batch},{LFM2_T},{heads},{d}\]\S* "
                         r"broadcast\(", text)


@pytest.mark.parametrize("batch", [1, 2])
def test_attention_compiles_at_ouro_and_olmoe_heads(spec, batch):
    """16 heads of 128 over 4096 positions, value and gradient, at 16
    batch-heads (Ouro's step) and at 32 (OLMoE's; in Ouro's program the
    parent's dQ kernel overran the default 16 MiB by 52 KB there): two
    kernels.  Only that it compiles: no cell's batch changes with it."""
    assert _kernels(_attention_grads(spec, batch, 4096, 16, 16, 128,
                                     with_value=True)) == 2


@pytest.mark.parametrize("seq,kernels", [(5120, 2), (16384, 3), (32768, 3)])
def test_long_sequences_keep_the_two_backward_kernels(spec, seq, kernels):
    """Where dK and dV of a whole sequence no longer fit the one-pass
    kernel's VMEM beside what else it holds (``benchmark/longctx.py``: 32k
    and 64k positions of 128 features) the backward is the two kernels."""
    assert _kernels(_attention_grads(spec, 1, seq, 2, 2, 128)) == kernels


def _longest_one_pass(d, block=1024):
    return max(t for t in range(block, 65536 + 1, block)
               if pallas_kernels._one_pass_fits(t, t, d, d, block, block))


@pytest.mark.parametrize("d", [64, 128])
@pytest.mark.parametrize("longer,route,kernels", [
    (0, "one_pass", 2), (1024, "two_pass", 3)], ids=["longest", "next"])
def test_the_longest_one_pass_sequence_compiles(spec, d, longer, route,
                                                kernels):
    """The longest sequence ``_one_pass_fits`` admits at 1024 x 1024 blocks
    (9 216 keys, of 64 features as of 128: a row takes whole lane tiles)
    compiles on the one-pass route within ``FLASH_BWD_VMEM_BYTES``, and the
    next block up takes the two kernels.  At 32 K / V heads XLA keeps no
    operand or result of the kernel in VMEM beside it (at two heads it keeps
    dK and dV there, and the kernel then needs 17 MiB less), so the kernel
    holds all that the rule counts."""
    heads = 32
    seq = _longest_one_pass(d) + longer
    before = dict(compile_cache.stats().snapshot())
    text = _attention_grads(spec, 1, seq, heads, heads, d)
    after = compile_cache.stats().snapshot()
    key = "route/flash_attention_bwd:" + route
    assert after.get(key, 0) - before.get(key, 0) == 1
    results = re.findall(rf"= \(((?:f32\[{heads},{seq},{d}\]\S*(?:, )?){{3}})"
                         r"\) custom-call\(", text)
    assert len(results) == (route == "one_pass")
    assert "S(1)" not in "".join(results)
    assert _kernels(text) == kernels


# the dropless ``moe`` lowering at the two cells' shapes: 8192 tokens of 2048
# features; LFM2 top-4 of a 32-wide router over the 8 experts of 1792 a chip
# holds, OLMoE top-8 of 64 experts of 1024, all held
@pytest.mark.parametrize(
    "experts,held,top_k,width,route,parent_temp,allowed", [
        (32, 8, 4, 1792, {"scoring": "sigmoid", "renormalize": True},
         1_553_075_712, 8_388_608),
        (64, 64, 8, 1024, {}, 1_823_088_640, 302_514_176)],
    ids=["lfm2-share", "olmoe"])
def test_dropless_moves_rows_with_the_tiles_in_use(
        spec, monkeypatch, experts, held, top_k, width, route, parent_temp,
        allowed):
    """``_dropless``, value and every gradient, compiles for the v5e at
    both cells' shapes, its row movement driven from the tiled side: no XLA
    gather makes a bound-sized array ([33792, 2048], [73728, 2048]; the
    rows come from ``rows_from_tokens``, two more kernels than the seven of
    the experts), and where a share is held (LFM2) ``moe.combine`` holds no
    [top_k, N, D] array either way ([4, 8192, 2048], [32768, 2048]: the
    tokens' sum is a scatter-add over the tiles in use, counted
    ``route/moe_rows:tiles``; every expert held: ``:take``).  Temporaries
    against the parent's (PR 34) for the same function: LFM2's share
    1 560 958 976 bytes for 1 553 075 712 (``down`` [33792, 2048] is kept
    for the backward pass where ``picked`` [4, 8192, 2048] was: 8 388 608
    more, and nothing else); OLMoE 2 125 192 192 for 1 823 088 640 (the
    same exchange, 67 MB, and that ``picked``'s 537 MB had found room in a
    gradient's output buffer where ``down``'s 604 MB do not: in the cell's
    whole step the difference is the 67 MB, 12.233 GB for 12.161).  Since
    PR 41 (the derivative of the activation a kernel over the tiles in use
    where XLA's fusion stood): LFM2's share 1 560 313 856, OLMoE
    2 125 159 936; the limits are PR 35's."""
    n, d = 8192, LFM2_D
    bound = (n * top_k // TILE + held) * TILE
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    def loss(x, router, gate, up, down, mix):
        out, aux, z = moe_ops._dropless(x, router, gate, up, down, top_k,
                                        jax.nn.silu, **route)
        return jnp.sum(out * mix) + aux + z

    counted = compile_cache.stats().snapshot()
    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4))).lower(
        spec((n, d)), spec((d, experts)), spec((held, d, width)),
        spec((held, d, width)), spec((held, width, d)),
        spec((n, d))).compile()
    form = "route/moe_rows:" + ("tiles" if held < experts else "take")
    assert compile_cache.stats().snapshot()[form] == counted.get(form, 0) + 1
    text = compiled.as_text()
    assert _kernels(text) == 9
    assert not re.search(rf"f32\[{bound},{d}\]\S* gather\(", text)
    if held < experts:
        picked = rf"f32\[({top_k},{n}|{top_k * n}),{d}\]"
        assert not [line for line in text.splitlines()
                    if "moe.combine" in line and re.search(picked, line)]
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp <= parent_temp + allowed, temp


# granite-4.0-h-micro's Mamba-2 layers at the cell's shapes: the filter over
# 4096 + 2 x 128 = 4352 channels (34 lane tiles) of 4 taps
GRANITE_C, GRANITE_TAPS = 4352, 4


@pytest.mark.parametrize("seq", [4096, 8192])
def test_ungated_short_conv_is_one_kernel_a_direction(spec, seq):
    """The ungated short convolution (bias, SiLU), value and the three
    gradients: one custom call a direction and next to no temporaries."""
    def loss(x, w, bias, mix):
        return jnp.sum(pallas_kernels.short_conv(x, w, bias, "silu") * mix)

    compiled = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2))).lower(
        spec((1, seq, GRANITE_C)), spec((GRANITE_C, GRANITE_TAPS)),
        spec((GRANITE_C,)), spec((1, seq, GRANITE_C))).compile()
    assert _kernels(compiled.as_text()) == 2
    assert compiled.memory_analysis().temp_size_in_bytes \
        < 0.5 * seq * GRANITE_C * 4


def test_ssd_scan_is_one_kernel_a_direction_inside_its_vmem(spec):
    """``ssd_scan`` at the Granite cell's shape (one 8192-token sequence, 64
    heads of 64, one group of 128 state features, chunks of 256), value and
    all six gradients: Mosaic takes both kernels' blocks (a chunk of eight
    heads, 512 lanes) within ``SSD_VMEM_BYTES`` of scoped VMEM, which is
    what the route's bound counts; one custom call a direction; what stands
    in HBM beside operands and gradients is the output, the chunks'
    boundary states (67 MB) and the head blocks' partial dBm / dCm (2 x 34
    MB), nothing of [chunks, heads, 256, 256] (537 MB)."""
    from paddle_tpu.ops import ssd_kernels

    b, t_len, heads, p, groups, n, chunk = 1, 8192, 64, 64, 1, 128, 256
    shapes = ((b, t_len, heads, p), (b, t_len, groups, n))
    lanes = ssd_kernels.heads_a_block(heads, groups, p) * p
    assert lanes == ssd_kernels.SSD_BLOCK_LANES
    assert ssd_kernels._blocks_fit(chunk, n, lanes)
    assert ssd_kernels.SSD_VMEM_BYTES <= 32 << 20

    def loss(*xs):
        return jnp.sum(ssd_kernels.ssd_scan(*xs, chunk) ** 2)

    operands = (spec(shapes[0]), spec((b, t_len, heads)), spec((heads,)),
                spec(shapes[1]), spec(shapes[1]), spec((heads,)))
    forward = jax.jit(lambda *xs: ssd_kernels.ssd_scan(*xs, chunk)).lower(
        *operands).compile()
    assert _kernels(forward.as_text()) == 1
    compiled = jax.jit(jax.value_and_grad(loss, argnums=tuple(range(6)))) \
        .lower(*operands).compile()
    assert _kernels(compiled.as_text()) == 2
    wide = b * t_len * heads * p * 4
    # (the 4-D operands' lanes are half full, so each is copied to the flat
    # layout a model hands over at no cost; with the 537 MB it would not fit)
    assert compiled.memory_analysis().temp_size_in_bytes < 5 * wide


def _cell_step(one_chip, monkeypatch, name, batch=None, **sizes_over):
    """(routes counted, bytes ``memory_analysis()`` gives, module text) of
    the K-step program of the cell whose configuration is ``name`` (found by
    its files, at the published widths and the timed sizes), lowered on
    shapes for the described v5e and compiled."""
    import importlib.util
    import json

    import paddle_tpu as pt

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    bench = os.path.join(root, "chipbench")
    with open(os.path.join(bench, "configs", f"{name}.json")) as fh:
        sizes = {**json.load(fh), **sizes_over}
    cell = next(c for c in (
        json.load(open(os.path.join(bench, "workloads", f)))
        for f in sorted(os.listdir(os.path.join(bench, "workloads"))))
        if c["config"] == sizes["name"])
    found = importlib.util.spec_from_file_location(
        f"{name}_config_for_compile",
        os.path.join(bench, "configs", f"{name}.py"))
    config = importlib.util.module_from_spec(found)
    found.loader.exec_module(config)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    batch, k = batch or cell["batch_per_chip"], cell["steps_per_window"]
    built = config.build("train", batch, sizes)
    main = built["main"]

    def shaped(shape, dtype):
        return jax.ShapeDtypeStruct(
            tuple(int(d) for d in shape),
            jax.dtypes.canonicalize_dtype(dtype), sharding=one_chip)

    feeds = {n: shaped((batch,) + tuple(s["shape"]), s["dtype"])
             for n, s in built["feeds"].items()}
    state = {v.name: shaped(v.shape, v.dtype)
             for v in main.global_block().vars.values() if v.persistable}
    before = dict(compile_cache.stats().snapshot())
    exe = pt.Executor(amp=built["amp"])
    # (products as a step on the chip has them: under conftest's 'highest'
    # the one-pass attention backward asks for more VMEM than it may take)
    with jax.default_matmul_precision("default"), exe._call_context(main):
        multi = exe._make_multi(main, [built["loss"]], False, k, False)
        step = exe._build_steps(main, multi, False, fingerprint=None)
        compiled = step._jit.lower(feeds, state, 0).compile()
    seen = {r: n - before.get(r, 0)
            for r, n in compile_cache.stats().snapshot().items()
            if r.startswith("route/")}
    m = compiled.memory_analysis()
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             - m.alias_size_in_bytes + m.temp_size_in_bytes
             + m.generated_code_size_in_bytes)
    return seen, total, compiled.as_text()


CHIP_BYTES = 16.9e9


def test_granite_cell_step_compiles_and_fits_the_chip(one_chip, monkeypatch):
    """The K-step program of the cell whose configuration holds an
    ``ssd_scan`` and no experts, lowered on shapes for the described v5e:
    nine ungated filters, nine ``ssd_scan``s and one grouped attention take
    the kernels, every layer is a recomputed stretch, and
    ``memory_analysis()`` puts the step between a quarter and 90 % of the
    chip's 16.9 GB: the rule the cell's sequence length was chosen by."""
    seen, total, _ = _cell_step(one_chip, monkeypatch, "granite_4_0_h_micro")
    assert seen["route/ssd_scan:pallas"] == 9
    assert "route/ssd_scan:xla" not in seen
    assert seen["route/short_conv:pallas"] == 9
    assert seen["route/flash_attention:grouped"] == 1
    assert seen["route/flash_attention_bwd:one_pass"] == 1
    assert seen["route/recompute:checkpoint"] == 10
    assert 0.25 * CHIP_BYTES < total < 0.9 * CHIP_BYTES, total


# NVIDIA-Nemotron-3-Nano-30B-A3B at the cell's shapes: 8192 positions of
# 2688 features; 8 un-gated experts of 1856 held under a 128-wide router, 6 a
# token; Mamba-2 at 8 groups of 128, chunks of 128; 32 query heads over 2
# key / value heads of 128
NEMO_T, NEMO_D, NEMO_H, NEMO_HELD, NEMO_TOP = 8192, 2688, 1856, 8, 6
NEMO_ROWS = (NEMO_T * NEMO_TOP // TILE + NEMO_HELD) * TILE


@pytest.mark.parametrize("transposed", [True, False],
                         ids=["held [E, H, D]", "held [E, D, H]"])
def test_ungated_experts_compile_at_1856_columns(spec, transposed):
    """The un-gated grouped products at an expert width that is 14.5 lane
    tiles (``_largest_tile`` finds no 128-multiple divisor of 1856, so a
    weight block is the whole [2688, 1856], 20 MB of ``GMM_VMEM_BYTES``
    double-buffered, and ``_tgmm``'s accumulator [896, 1856]): forward, the
    rows' gradient and the stacks' gradient of up and of down, six kernels,
    Mosaic takes them with the up stack held either way.  Held [E, D, H] it
    compiles too, but the device would lay that array out with D minor and
    copy it for the kernel (the whole step below holds no such array)."""
    relu2 = moe_ops._ACTS["relu2"]
    layout = (spec((NEMO_ROWS // TILE,), jnp.int32), spec((1,), jnp.int32))

    def loss(lhs, up, down, tile_group, num_tiles):
        hidden = relu2(pallas_kernels._grouped(
            lhs, up, tile_group, num_tiles, False, transposed))
        out = pallas_kernels._grouped(hidden, down, tile_group, num_tiles,
                                      False)
        return jnp.sum(out * out)

    up = (NEMO_HELD, NEMO_H, NEMO_D) if transposed else \
        (NEMO_HELD, NEMO_D, NEMO_H)
    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        spec((NEMO_ROWS, NEMO_D)), spec(up),
        spec((NEMO_HELD, NEMO_H, NEMO_D)), *layout).compile()
    assert _kernels(compiled.as_text()) == 6


@pytest.mark.parametrize(
    "d,width,experts,top_k,gated,act,route", [
        (NEMO_D, NEMO_H, 128, NEMO_TOP, False, "relu2",
         {"scoring": "sigmoid", "renormalize": True, "routed_scale": 2.5,
          "up_transposed": True}),
        (LFM2_D, 1792, 32, 4, True, "silu",
         {"scoring": "sigmoid", "renormalize": True})],
    ids=["nemotron [8, 1856, 2688] transposed", "lfm2 [8, 2048, 1792] pair"])
def test_the_experts_stage_is_kernels_over_the_tiles_in_use(
        spec, monkeypatch, d, width, experts, top_k, gated, act, route):
    """``_dropless`` with a share of 8 experts held, value and every
    gradient, at the two cells' shapes (8192 tokens): the forward product
    with the activation in its epilogue, the derivative's kernel and the
    two backward products compile within ``GMM_VMEM_BYTES``, their grids'
    row axis ending at ``num_tiles``, Nemotron's up stack read transposed;
    seven kernels of the experts (``down``'s three among them) and two of
    the row movement.  Every instruction under
    ``moe.experts``, forward and backward, is a kernel (or the pick of one
    of its results): XLA computes nothing there, and no fusion of the module
    makes an array of the bound's [rows, H], which would read rows past
    ``num_tiles`` that no kernel writes."""
    n, held = NEMO_T, NEMO_HELD
    bound = (n * top_k // TILE + held) * TILE
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    def loss(x, router, stacks, mix):
        out, aux, z = moe_ops._dropless(
            x, router, stacks.get("gate"), stacks["up"], stacks["down"],
            top_k, moe_ops._ACTS[act], **route)
        return jnp.sum(out * mix) + aux + z

    up = (held, width, d) if route.get("up_transposed") else (held, d, width)
    stacks = {"up": spec(up), "down": spec((held, width, d)),
              **({"gate": spec(up)} if gated else {})}
    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        spec((n, d)), spec((d, experts)), stacks,
        spec((n, d))).compile().as_text()
    assert _kernels(text) == 9
    inside = [re.match(r"\s*(?:ROOT )?\S+ = .*? ([\w-]+)\(", line).group(1)
              for line in text.splitlines()
              if re.search(r'op_name="[^"]*moe\.experts', line)]
    assert inside.count("custom-call") == 7
    assert set(inside) <= {"custom-call", "get-tuple-element"}, inside
    assert not re.search(rf"f32\[{bound},{width}\]\S* fusion\(", text)


def test_ssd_scan_kernels_compile_at_eight_groups(spec):
    """``ssd_scan`` at the Nemotron cell's shape (one 8192-token sequence,
    64 heads of 64 over 8 groups of 128 state features, chunks of 128),
    value and all six gradients: a head block is one group's eight heads
    (512 lanes), Bm and Cm are read through the group's index map, one
    custom call a direction."""
    from paddle_tpu.ops import ssd_kernels

    b, heads, p, groups, n, chunk = 1, 64, 64, 8, 128, 128
    shapes = ((b, NEMO_T, heads, p), (b, NEMO_T, groups, n))
    assert ssd_kernels.heads_a_block(heads, groups, p) * p \
        == ssd_kernels.SSD_BLOCK_LANES
    operands = (spec(shapes[0]), spec((b, NEMO_T, heads)), spec((heads,)),
                spec(shapes[1]), spec(shapes[1]), spec((heads,)))

    def loss(*xs):
        return jnp.sum(ssd_kernels.ssd_scan(*xs, chunk) ** 2)

    forward = jax.jit(lambda *xs: ssd_kernels.ssd_scan(*xs, chunk)).lower(
        *operands).compile()
    assert _kernels(forward.as_text()) == 1
    compiled = jax.jit(jax.value_and_grad(loss, argnums=tuple(range(6)))) \
        .lower(*operands).compile()
    assert _kernels(compiled.as_text()) == 2
    assert compiled.memory_analysis().temp_size_in_bytes \
        < 5 * b * NEMO_T * heads * p * 4


def test_attention_at_two_kv_heads_of_128_takes_the_one_pass_backward(spec):
    """32 query heads over 2 key / value heads of 128 at 8192 positions:
    dK / dV of a sequence fit the one-pass kernel beside what else it holds
    (``_one_pass_fits`` admits 9 216 keys of 128 features; Mosaic asks 26.6
    MiB inside Nemotron's step), so the backward is one kernel; TWO custom
    calls, dK and dV at the 2 heads they have, no K / V broadcast to 32."""
    heads, kv_heads, d = 32, 2, 128
    before = dict(compile_cache.stats().snapshot())
    text = _attention_grads(spec, 1, NEMO_T, heads, kv_heads, d)
    after = compile_cache.stats().snapshot()
    assert after.get("route/flash_attention_bwd:one_pass", 0) \
        - before.get("route/flash_attention_bwd:one_pass", 0) == 1
    assert after.get("route/flash_attention_bwd:two_pass", 0) \
        == before.get("route/flash_attention_bwd:two_pass", 0)
    assert _kernels(text) == 2
    results = re.findall(
        rf"\(f32\[{heads},{NEMO_T},{d}\]\S*, "
        rf"f32\[{kv_heads},{NEMO_T},{d}\]\S*, "
        rf"f32\[{kv_heads},{NEMO_T},{d}\]\S*\) custom-call\(", text)
    assert len(results) == 1
    assert not re.search(rf"f32\[1,{NEMO_T},{heads},{d}\]\S* broadcast\(",
                         text)


def test_nemotron_cell_step_compiles_and_fits_the_chip(one_chip, monkeypatch):
    """The K-step program of the cell whose configuration holds sparse
    experts beside ``ssd_scan``: four filters and four ``ssd_scan``s at eight
    groups, the grouped attention with its one-pass backward and four
    ``moe`` ops (a share, un-gated, a shared expert each) take the kernels;
    six of the nine layers are recomputed stretches, the least that fits;
    ``memory_analysis()`` puts the step between a quarter and 90 % of the
    chip's 16.9 GB.  The up stacks are held [8, 1856, 2688] and the module
    holds no [8, 2688, 1856] array at all: held that way the scan's carried
    state stood a second time, lane-padded, beside its arguments (12 x 165
    MB) and the step read 15.31 GB with EVERY layer recomputed."""
    name = "nemotron_3_nano_30b_a3b"
    seen, total, text = _cell_step(one_chip, monkeypatch, name)
    assert seen["route/ssd_scan:pallas"] == 4
    assert seen["route/short_conv:pallas"] == 4
    assert seen["route/flash_attention:grouped"] == 1
    assert seen["route/flash_attention_bwd:one_pass"] == 1
    assert seen.get("route/flash_attention_bwd:two_pass", 0) == 0
    for route in ("dropless", "sigmoid", "share", "single", "shared"):
        assert seen["route/moe:" + route] == 4, route
    assert seen["route/moe_rows:tiles"] == 4
    assert seen["route/recompute:checkpoint"] == 6
    assert not {r for r, n in seen.items() if n and r.endswith(
        (":xla", ":gated_pair", ":reference"))}
    assert 0.25 * CHIP_BYTES < total < 0.9 * CHIP_BYTES, total
    assert f"[{NEMO_HELD},{NEMO_D},{NEMO_H}]" not in text
    assert f"f32[{NEMO_HELD},{NEMO_H},{NEMO_D}]" in text


def test_two_nemotron_sequences_do_not_fit_with_every_layer_recomputed(
        one_chip, monkeypatch):
    """Two 8192-token sequences a step, every layer a recomputed stretch
    (the most the program can give back): over 90 % of the chip, which is
    why the cell runs one."""
    _, total, _ = _cell_step(one_chip, monkeypatch, "nemotron_3_nano_30b_a3b",
                             batch=2, recompute=True)
    assert total > 0.9 * CHIP_BYTES, total

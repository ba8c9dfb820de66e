"""Pallas flash-attention kernel tests (interpret mode on the CPU mesh;
the same kernel compiles for the MXU on TPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu import profiler
from paddle_tpu.ops import pallas_kernels
from paddle_tpu.ops.pallas_kernels import (_reference_attention,
                                           flash_attention,
                                           flash_attention_with_lse)

R = np.random.RandomState(4)


def _ref(q, k, v, causal, scale):
    return np.asarray(_reference_attention(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), causal, scale))


@pytest.mark.parametrize("causal", [False, True])
def test_flash_matches_reference(causal):
    BH, T, D = 2, 128, 32
    q = R.randn(BH, T, D).astype("float32")
    k = R.randn(BH, T, D).astype("float32")
    v = R.randn(BH, T, D).astype("float32")
    out = flash_attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                          causal=causal, block_q=64, block_k=64,
                          use_pallas=True, interpret=True)
    np.testing.assert_allclose(np.asarray(out),
                               _ref(q, k, v, causal, D ** -0.5),
                               atol=2e-5, rtol=2e-5)


def test_flash_bhtd_layout():
    B, T, H, D = 2, 64, 4, 16
    q = R.randn(B, T, H, D).astype("float32")
    k = R.randn(B, T, H, D).astype("float32")
    v = R.randn(B, T, H, D).astype("float32")
    out = flash_attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                          block_q=64, block_k=64, use_pallas=True,
                          interpret=True)
    assert out.shape == (B, T, H, D)
    # per-head equivalence
    for h in range(H):
        np.testing.assert_allclose(
            np.asarray(out[:, :, h]),
            _ref(q[:, :, h].transpose(0, 1, 2), k[:, :, h], v[:, :, h],
                 False, D ** -0.5), atol=2e-5, rtol=2e-5)


def test_flash_gradient_matches_reference():
    BH, T, D = 1, 64, 16
    q = jnp.asarray(R.randn(BH, T, D).astype("float32"))
    k = jnp.asarray(R.randn(BH, T, D).astype("float32"))
    v = jnp.asarray(R.randn(BH, T, D).astype("float32"))

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, block_q=32, block_k=32,
                                       use_pallas=True, interpret=True) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(_reference_attention(q, k, v, False, D ** -0.5) ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-4, rtol=1e-4)


def test_flash_cross_attention_kernel():
    """Tq != Tk and Dv != Dq run through the kernel itself (encoder-decoder
    attention): the key-block count must come from K's length and the output
    feature dim from V's."""
    BH, Tq, Tk, D, Dv = 2, 64, 128, 16, 32
    q = R.randn(BH, Tq, D).astype("float32")
    k = R.randn(BH, Tk, D).astype("float32")
    v = R.randn(BH, Tk, Dv).astype("float32")
    out = flash_attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                          block_q=64, block_k=64, use_pallas=True,
                          interpret=True)
    assert out.shape == (BH, Tq, Dv)
    np.testing.assert_allclose(np.asarray(out),
                               _ref(q, k, v, False, D ** -0.5),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("D,Dv", [(192, 128), (64, 32)])
def test_flash_value_head_size_of_its_own(D, Dv):
    """V's head size differs from Q's and K's (latent attention's 192 / 128):
    the kernel's output is [B, T, H, Dv], equal to the formula's, and the
    call is counted as ``route/flash_attention:value_dims``."""
    B, T, H = 1, 256, 2
    q, k = (jnp.asarray(R.randn(B, T, H, D), jnp.float32) for _ in range(2))
    v = jnp.asarray(R.randn(B, T, H, Dv), jnp.float32)
    before = profiler.compile_stats().snapshot().get(
        "route/flash_attention:value_dims", 0)
    out = flash_attention(q, k, v, causal=True, block_q=128, block_k=128,
                          use_pallas=True, interpret=True)
    assert out.shape == (B, T, H, Dv)
    assert profiler.compile_stats().snapshot()[
        "route/flash_attention:value_dims"] == before + 1
    for h in range(H):
        np.testing.assert_allclose(
            np.asarray(out[:, :, h]),
            _ref(np.asarray(q[:, :, h]), np.asarray(k[:, :, h]),
                 np.asarray(v[:, :, h]), True, D ** -0.5),
            atol=2e-5, rtol=2e-5)


def test_flash_causal_cross_falls_back():
    BH, Tq, Tk, D = 1, 64, 128, 16
    q = R.randn(BH, Tq, D).astype("float32")
    k = R.randn(BH, Tk, D).astype("float32")
    out = flash_attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(k),
                          causal=True, block_q=64, block_k=64,
                          use_pallas=True, interpret=True)
    np.testing.assert_allclose(np.asarray(out),
                               _ref(q, k, k, True, D ** -0.5),
                               atol=2e-5, rtol=2e-5)


def test_flash_ragged_tail_falls_back():
    BH, T, D = 1, 100, 16     # not a block multiple
    q = R.randn(BH, T, D).astype("float32")
    out = flash_attention(jnp.asarray(q), jnp.asarray(q), jnp.asarray(q),
                          block_q=64, block_k=64, use_pallas=True,
                          interpret=True)
    np.testing.assert_allclose(np.asarray(out),
                               _ref(q, q, q, False, D ** -0.5),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_gradient_kernel_paths(causal):
    """Gradients flow through the fused Pallas dq and dk/dv kernels (not a
    jnp recompute): multi-block grids in both q and k so block accumulation,
    lse residuals, and causal block-skipping are all exercised."""
    BH, T, D = 2, 128, 16
    q = jnp.asarray(R.randn(BH, T, D).astype("float32"))
    k = jnp.asarray(R.randn(BH, T, D).astype("float32"))
    v = jnp.asarray(R.randn(BH, T, D).astype("float32"))
    w = jnp.asarray(R.randn(BH, T, D).astype("float32"))

    def loss_flash(q, k, v):
        return jnp.sum(w * flash_attention(
            q, k, v, causal=causal, block_q=32, block_k=32,
            use_pallas=True, interpret=True))

    def loss_ref(q, k, v):
        return jnp.sum(w * _reference_attention(q, k, v, causal, D ** -0.5))

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-4, rtol=1e-4)


def test_flash_gradient_cross_attention():
    """Tq != Tk and Dv != D through the backward kernels."""
    BH, Tq, Tk, D, Dv = 1, 64, 128, 16, 32
    q = jnp.asarray(R.randn(BH, Tq, D).astype("float32"))
    k = jnp.asarray(R.randn(BH, Tk, D).astype("float32"))
    v = jnp.asarray(R.randn(BH, Tk, Dv).astype("float32"))

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, block_q=32, block_k=32,
                                       use_pallas=True, interpret=True) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(_reference_attention(q, k, v, False, D ** -0.5) ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-4, rtol=1e-4)


# ---------------------------------------------------------------------------
# the backward's two routes (one kernel a tile visit; the two kernels)
# ---------------------------------------------------------------------------
def _bwd_routes():
    return {k.rsplit(":", 1)[1]: v
            for k, v in profiler.compile_stats().snapshot().items()
            if k.startswith("route/flash_attention_bwd:")}


def _attention_and_lse(q, k, v, causal, scale):
    """``_reference_attention`` with the rows' logsumexp beside it."""
    group = q.shape[0] // k.shape[0]
    k, v = jnp.repeat(k, group, axis=0), jnp.repeat(v, group, axis=0)
    s = jnp.einsum("bqd,bkd->bqk", q * scale, k)
    if causal:
        s = jnp.where(jnp.tril(jnp.ones(s.shape[-2:], bool))[None], s,
                      pallas_kernels.NEG_INF)
    lse = jax.nn.logsumexp(s, axis=-1, keepdims=True)
    return jnp.einsum("bqk,bkd->bqd", jnp.exp(s - lse), v), lse


# heads, K/V heads, Tq, Tk, D, Dv, causal, (block_q, block_k), a cotangent
# on lse, the route the rule gives the shape
BWD_CASES = {
    "causal": (2, 2, 256, 256, 16, 16, True, (128, 128), False, "one_pass"),
    "full": (2, 2, 256, 256, 16, 16, False, (128, 128), False, "one_pass"),
    # the cell's shape in small: 4 query heads to a K/V head of 64
    "grouped_causal": (8, 2, 384, 384, 64, 64, True, (128, 128), False,
                       "one_pass"),
    "grouped_full": (4, 1, 256, 256, 64, 64, False, (128, 128), False,
                     "one_pass"),
    "blocks_differ": (2, 1, 512, 512, 16, 16, True, (128, 256), False,
                      "one_pass"),
    "cross": (2, 2, 128, 384, 16, 32, False, (128, 128), False, "one_pass"),
    "one_block": (3, 3, 64, 64, 16, 16, True, (64, 64), False, "one_pass"),
    "lse_causal": (2, 2, 256, 256, 16, 16, True, (128, 128), True,
                   "one_pass"),
    "lse_grouped_full": (4, 2, 128, 256, 16, 32, False, (128, 128), True,
                         "one_pass"),
    # Nemotron's grouping in small: 16 query heads to one K/V head of 128
    "grouped_16_causal": (16, 1, 512, 512, 128, 128, True, (128, 128),
                          False, "one_pass"),
    # latent attention: q and k of 192 (whole lane tiles of 256 in VMEM),
    # v of 128; and 64 / 32
    "value_dims_192": (4, 4, 256, 256, 192, 128, True, (128, 128), False,
                       "one_pass"),
    "value_dims_64": (2, 2, 256, 256, 64, 32, True, (128, 128), False,
                      "one_pass"),
    # a row of lse cannot be cut into blocks of 32: the two kernels
    "small_blocks": (4, 2, 128, 128, 16, 16, True, (32, 32), False,
                     "two_pass"),
    "small_blocks_lse": (2, 2, 64, 128, 16, 32, False, (32, 64), True,
                         "two_pass"),
}


def _grads_against_formula(heads, kv_heads, Tq, Tk, D, Dv, causal, blocks,
                           with_lse, seed):
    """dQ, dK and dV of the kernels (interpreted) against autodiff of the
    plain formula; the backward routes the call counted."""
    rng = np.random.RandomState(seed)
    q = jnp.asarray(rng.randn(heads, Tq, D), jnp.float32)
    k = jnp.asarray(rng.randn(kv_heads, Tk, D), jnp.float32)
    v = jnp.asarray(rng.randn(kv_heads, Tk, Dv), jnp.float32)
    w = jnp.asarray(rng.randn(heads, Tq, Dv), jnp.float32)
    u = jnp.asarray(rng.randn(heads, Tq, 1), jnp.float32)
    scale = D ** -0.5

    def kernels(q, k, v):
        if not with_lse:
            return jnp.sum(w * flash_attention(
                q, k, v, causal=causal, block_q=blocks[0],
                block_k=blocks[1], use_pallas=True, interpret=True))
        out, lse = flash_attention_with_lse(
            q, k, v, causal=causal, block_q=blocks[0], block_k=blocks[1],
            interpret=True)
        return jnp.sum(w * out) + jnp.sum(u * lse)

    def formula(q, k, v):
        out, lse = _attention_and_lse(q, k, v, causal, scale)
        return jnp.sum(w * out) + (jnp.sum(u * lse) if with_lse else 0.0)

    before = _bwd_routes()
    got = jax.grad(kernels, argnums=(0, 1, 2))(q, k, v)
    seen = {r: n - before.get(r, 0) for r, n in _bwd_routes().items()}
    want = jax.grad(formula, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(got, want):
        assert a.shape == b.shape and a.dtype == b.dtype
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-4, rtol=1e-4)
    return {r: n for r, n in seen.items() if n}


@pytest.mark.parametrize("case", sorted(BWD_CASES))
def test_flash_backward_routes_match_reference(case):
    """dQ, dK and dV of both routes (interpreted) against autodiff of the
    plain formula: several blocks in both directions, so accumulation
    across blocks, over a group's query heads and the causal skip are all
    exercised; the route is the one the rule gives the static shape."""
    *shape, route = BWD_CASES[case]
    assert _grads_against_formula(*shape, seed=len(case)) == {route: 1}


@pytest.mark.parametrize("causal,with_lse", [(True, False), (False, True)])
def test_flash_backward_with_raised_vmem_matches_reference(
        monkeypatch, causal, with_lse):
    """Latent attention's head sizes (q and k of 192, v of 128) where the
    one kernel does not fit the scoped VMEM every call asks for and does fit
    the most it may ask for (the two limits lowered so that 1 024 keys stand
    for the cell's 8 192): the one kernel, asking for its count rounded up
    to a whole MiB."""
    monkeypatch.setattr(pallas_kernels, "FLASH_BWD_VMEM_BYTES", 16 << 20)
    monkeypatch.setattr(pallas_kernels, "FLASH_BWD_ONE_PASS_MAX_BYTES",
                        24 << 20)
    shape = (2, 2, 1024, 1024, 192, 128, causal, (1024, 1024), with_lse)
    assert pallas_kernels._bwd_route(*shape[2:6], *shape[7]) == (
        "one_pass", 21 << 20)
    assert _grads_against_formula(*shape, seed=3) == {
        "one_pass": 1, "raised_vmem": 1}


MIB = 1 << 20


@pytest.mark.parametrize("T,D,Dv,blocks,want", [
    (8192, 192, 128, (1024, 1024), ("one_pass", 42 * MIB)),  # kimi-linear-
    (8192, 64, 64, (1024, 1024), ("one_pass", 32 * MIB)),    # lfm2-, granite-
    (4096, 128, 128, (1024, 1024), ("one_pass", 32 * MIB)),  # olmoe-, ouro-
    (8192, 128, 128, (1024, 1024), ("one_pass", 32 * MIB)),  # nemotron-
    (9216, 128, 128, (1024, 1024), ("one_pass", 32 * MIB)),
    (10240, 128, 128, (1024, 1024), ("one_pass", 34 * MIB)),
    (14336, 64, 64, (1024, 1024), ("one_pass", 42 * MIB)),
    (15360, 128, 128, (1024, 1024), ("two_pass", 32 * MIB)),
    (32768, 128, 128, (1024, 1024), ("two_pass", 32 * MIB)),
    (8192, 192, 128, (512, 512), ("one_pass", 32 * MIB)),
    (4096, 128, 128, (2048, 1024), ("one_pass", 34 * MIB)),  # a tile's
    (4096, 128, 128, (2048, 2048), ("two_pass", 32 * MIB)),  # temporaries
    (256, 16, 16, (32, 32), ("two_pass", 32 * MIB)),         # no row blocks
])
def test_flash_backward_vmem_is_a_rule_on_static_shape(T, D, Dv, blocks,
                                                       want):
    """The one kernel in the scoped VMEM every call asks for where it fits
    there; else the one kernel asking for its count rounded up to a whole
    MiB where that is within the most it may ask for; else the two kernels.
    The five other attention cells keep the 32 MiB they asked for."""
    assert pallas_kernels._bwd_route(T, T, D, Dv, *blocks) == want


@pytest.mark.parametrize("Tq,Tk,D,Dv,blocks,fits", [
    (8192, 8192, 64, 64, (1024, 1024), True),        # lfm2-, granite-
    (4096, 4096, 128, 128, (1024, 1024), True),      # olmoe-, ouro-
    (8192, 8192, 128, 128, (1024, 1024), True),      # nemotron-train-scan
    (9216, 9216, 128, 128, (1024, 1024), True),      # the longest at 1024
    (8192, 8192, 192, 128, (1024, 1024), False),     # kimi-linear-train-scan
    (10240, 10240, 128, 128, (1024, 1024), False),
    (9216, 9216, 64, 64, (1024, 1024), True),        # 64 features take
    (10240, 10240, 64, 64, (1024, 1024), False),     # the lanes 128 do
    (12288, 12288, 64, 64, (1024, 1024), False),     # Mosaic asks 35.6 MiB
    (13824, 13824, 128, 128, (512, 512), True),      # the longest at 512
    (14336, 14336, 128, 128, (512, 512), False),
    (32768, 32768, 128, 128, (1024, 1024), False),   # benchmark/longctx.py
    (65536, 65536, 128, 128, (1024, 1024), False),
    (65536, 65536, 64, 64, (512, 512), False),
    (4096, 4096, 128, 128, (2048, 1024), False),     # a tile's temporaries
    (1024, 16384, 128, 128, (512, 512), False),      # Tk alone is what counts
    (256, 256, 16, 16, (32, 32), False),             # no row blocks of 32
    (32, 256, 16, 16, (32, 32), True),               # one query block
])
def test_flash_backward_route_is_a_rule_on_static_shape(Tq, Tk, D, Dv,
                                                       blocks, fits):
    assert pallas_kernels._one_pass_fits(Tq, Tk, D, Dv, *blocks) is fits


# ---------------------------------------------------------------------------
# the block-diffusion mask: the noisy stream at 0..L-1, the clean at L..2L-1
# ---------------------------------------------------------------------------
def _four_rules(L, b):
    """[2L, 2L] bool, position by position from the mask's four rules."""
    allowed = np.zeros((2 * L, 2 * L), bool)
    for i in range(2 * L):
        for j in range(2 * L):
            qb, kb = (i % L) // b, (j % L) // b
            if i < L and j < L:              # noisy -> noisy: its block
                allowed[i, j] = kb == qb
            elif i < L:                      # noisy -> clean: blocks before
                allowed[i, j] = kb < qb
            elif j >= L:                     # clean -> clean: causal by block
                allowed[i, j] = kb <= qb
    return allowed                           # clean -> noisy: never


@pytest.mark.parametrize("L,b", [(16, 1), (16, 4), (32, 16), (64, 4)])
def test_the_mask_is_its_four_rules_and_holds_L2_plus_Lb(L, b):
    got = np.asarray(pallas_kernels.block_diffusion_allowed(
        pallas_kernels.BlockDiffusionMask(b, L), 2 * L))
    np.testing.assert_array_equal(got, _four_rules(L, b))
    assert got.sum() == L * L + L * b


def _diffusion_qkv(L, heads, kv_heads, D, seed):
    rng = np.random.RandomState(seed)
    return [jnp.asarray(rng.randn(h, 2 * L, D), jnp.float32)
            for h in (heads, kv_heads, kv_heads, heads)]


def _flash_routes():
    return {k: v for k, v in profiler.compile_stats().snapshot().items()
            if k.startswith("route/flash_attention")}


# L, block, (block_q, block_k), query heads, K/V heads, D, backward route
MASK_CASES = {
    # a block of one position: clean -> clean is the causal mask
    "b1": (64, 1, (32, 32), 2, 2, 16, "two_pass"),
    # tiles that hold many blocks, cut along the diagonal
    "b4_tiles_hold_blocks": (128, 4, (128, 128), 4, 2, 16, "one_pass"),
    "b4_blocks_differ": (128, 4, (128, 64), 2, 1, 32, "one_pass"),
    # a tile inside one block: every tile skipped or whole
    "b16_tile_inside_a_block": (64, 16, (8, 8), 2, 2, 16, "two_pass"),
    # a block boundary through the middle of a query tile, not a key tile
    "b16_boundary_cuts_the_tile": (64, 16, (32, 16), 4, 2, 16, "two_pass"),
    "b16_one_pass_grouped": (128, 16, (128, 32), 8, 2, 16, "one_pass"),
}


@pytest.mark.parametrize("case", sorted(MASK_CASES))
def test_block_diffusion_kernels_match_the_masked_softmax(case):
    """Forward and both backward routes of the kernels under the
    block-diffusion mask (interpreted) against a dense softmax under the
    mask written as its four rules, and autodiff of it: tiles skipped,
    whole and cut in every direction; the route is the rule's at 2L keys
    and is counted as any call's, with the mask's kind beside it."""
    L, b, (bq, bk), heads, kv_heads, D, route = MASK_CASES[case]
    q, k, v, w = _diffusion_qkv(L, heads, kv_heads, D, seed=len(case))
    mask = pallas_kernels.BlockDiffusionMask(b, L)
    allowed = jnp.asarray(_four_rules(L, b))

    def dense(q, k, v):
        group = q.shape[0] // k.shape[0]
        k, v = jnp.repeat(k, group, axis=0), jnp.repeat(v, group, axis=0)
        s = jnp.einsum("bqd,bkd->bqk", q, k) * D ** -0.5
        p = jax.nn.softmax(jnp.where(allowed[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("bqk,bkd->bqd", p, v)

    def kernels(q, k, v):
        return flash_attention(q, k, v, block_q=bq, block_k=bk,
                               interpret=True, mask=mask)

    np.testing.assert_allclose(np.asarray(kernels(q, k, v)),
                               np.asarray(dense(q, k, v)),
                               atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(
        np.asarray(_reference_attention(q, k, v, mask, D ** -0.5)),
        np.asarray(dense(q, k, v)), atol=2e-5, rtol=2e-5)
    before = _flash_routes()
    got = jax.grad(lambda *a: jnp.sum(w * kernels(*a)),
                   argnums=(0, 1, 2))(q, k, v)
    seen = {r: n - before.get(r, 0) for r, n in _flash_routes().items()
            if n != before.get(r, 0)}
    want = jax.grad(lambda *a: jnp.sum(w * dense(*a)),
                    argnums=(0, 1, 2))(q, k, v)
    for a, c in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(c),
                                   atol=1e-4, rtol=1e-4)
    assert seen == {"route/flash_attention:interpret": 1,
                    "route/flash_attention:block_diffusion": 1,
                    "route/flash_attention_bwd:" + route: 1,
                    "route/flash_attention_bwd:block_diffusion": 1,
                    **({"route/flash_attention:grouped": 1}
                       if heads != kv_heads else {})}


@pytest.mark.parametrize("bq,bk", [(32, 32), (64, 16)])
def test_a_block_of_one_is_causal_attention_over_the_clean_stream(bq, bk):
    """With a block of one position the clean stream's queries see the
    clean keys up to themselves and nothing else: its rows, value and
    gradient, are the causal kernels' over the clean stream alone."""
    L, D = 64, 16
    q, k, v, w = _diffusion_qkv(L, 2, 2, D, seed=9)
    mask = pallas_kernels.BlockDiffusionMask(1, L)

    def clean_rows(q, k, v):
        return flash_attention(q, k, v, block_q=bq, block_k=bk,
                               interpret=True, mask=mask)[:, L:]

    def causal(q, k, v):
        return flash_attention(q[:, L:], k[:, L:], v[:, L:], causal=True,
                               block_q=bq, block_k=bk, use_pallas=True,
                               interpret=True)

    np.testing.assert_allclose(np.asarray(clean_rows(q, k, v)),
                               np.asarray(causal(q, k, v)),
                               atol=2e-6, rtol=2e-6)
    for a, c in zip(
            jax.grad(lambda *x: jnp.sum(w[:, L:] * clean_rows(*x)),
                     argnums=(0, 1, 2))(q, k, v),
            jax.grad(lambda *x: jnp.sum(w[:, L:] * causal(*x)),
                     argnums=(0, 1, 2))(q, k, v)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(c),
                                   atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("L,b,bq,bk", [(64, 4, 32, 32), (64, 16, 8, 16),
                                       (64, 16, 32, 8), (128, 1, 64, 32),
                                       (128, 32, 64, 64)])
def test_each_tile_is_skipped_whole_or_cut_as_the_mask_holds_it(L, b, bq,
                                                                 bk):
    """The kernels' tile predicates against the dense mask: a tile is
    skipped where it allows no entry, whole where it allows all of them,
    cut (an element mask) where it allows some."""
    mask = pallas_kernels.BlockDiffusionMask(b, L)
    allowed = _four_rules(L, b)
    kinds = {"skipped": 0, "whole": 0, "cut": 0}
    for j in range(2 * L // bq):
        for kb in range(2 * L // bk):
            tile = pallas_kernels._diffusion_tile(
                mask, jnp.int32(j * bq), bq, jnp.int32(kb * bk), bk)
            part = allowed[j * bq:(j + 1) * bq, kb * bk:(kb + 1) * bk]
            want = ("whole" if part.all() else "cut" if part.any()
                    else "skipped")
            got = ("whole" if bool(tile.whole) else "cut" if bool(tile.cut)
                   else "skipped")
            assert got == want, (j, kb)
            kinds[got] += 1
            if got == "cut":
                keep = pallas_kernels._diffusion_keep(mask, tile, part.shape,
                                                      False)
                np.testing.assert_array_equal(np.asarray(keep), part)
    assert kinds["skipped"] > 0 and kinds["whole"] + kinds["cut"] > 0


def test_block_diffusion_routes_are_counted_with_the_mask_kind():
    """The kernels (interpreted) count ``:interpret``, the reference
    ``:reference``, each with ``:block_diffusion`` beside it."""
    L, D = 32, 16
    q, k, v, _ = _diffusion_qkv(L, 2, 2, D, seed=1)
    mask = pallas_kernels.BlockDiffusionMask(4, L)
    before = dict(profiler.compile_stats().snapshot())
    a = flash_attention(q, k, v, block_q=16, block_k=16, interpret=True,
                        mask=mask)
    c = flash_attention(q, k, v, use_pallas=False, mask=mask)
    seen = {r: n - before.get(r, 0)
            for r, n in profiler.compile_stats().snapshot().items()
            if n != before.get(r, 0) and r.startswith("route/")}
    assert seen == {"route/flash_attention:interpret": 1,
                    "route/flash_attention:reference": 1,
                    "route/flash_attention:block_diffusion": 2}
    np.testing.assert_allclose(np.asarray(a), np.asarray(c), atol=2e-5,
                               rtol=2e-5)
    with pytest.raises(ValueError, match="no power of two"):
        flash_attention(q, k, v, interpret=True,
                        mask=pallas_kernels.BlockDiffusionMask(3, L))
    with pytest.raises(ValueError, match="not causal"):
        flash_attention(q, k, v, causal=True, interpret=True, mask=mask)

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
    # a row of lse cannot be cut into blocks of 32: the two kernels
    "small_blocks": (4, 2, 128, 128, 16, 16, True, (32, 32), False,
                     "two_pass"),
    "small_blocks_lse": (2, 2, 64, 128, 16, 32, False, (32, 64), True,
                         "two_pass"),
}


@pytest.mark.parametrize("case", sorted(BWD_CASES))
def test_flash_backward_routes_match_reference(case):
    """dQ, dK and dV of both routes (interpreted) against autodiff of the
    plain formula: several blocks in both directions, so accumulation
    across blocks, over a group's query heads and the causal skip are all
    exercised; the route is the one the rule gives the static shape."""
    heads, kv_heads, Tq, Tk, D, Dv, causal, blocks, with_lse, route = \
        BWD_CASES[case]
    rng = np.random.RandomState(len(case))
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
    assert seen.get(route) == 1 and sum(seen.values()) == 1
    want = jax.grad(formula, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(got, want):
        assert a.shape == b.shape and a.dtype == b.dtype
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("Tq,Tk,D,Dv,blocks,fits", [
    (8192, 8192, 64, 64, (1024, 1024), True),        # lfm2-, granite-
    (4096, 4096, 128, 128, (1024, 1024), True),      # olmoe-, ouro-
    (8192, 8192, 128, 128, (1024, 1024), True),      # nemotron-train-scan
    (9216, 9216, 128, 128, (1024, 1024), True),      # the longest at 1024
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

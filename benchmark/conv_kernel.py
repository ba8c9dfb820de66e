"""Per-op A/B microbenchmark: XLA's conv emitter vs the hand-written
Pallas 1x1-conv kernels (ops/pallas_conv.py) on ResNet-50's eligible
1x1 shapes — the workload RESULTS.md round 5 identified as the binding
constraint (1x1/gradient convs at ~51 TFLOP/s against a 57-115 TFLOP/s
corrected-roofline ceiling).

Per (shape, pass) row both implementations run the identical math:

    fwd    out = conv1x1(x, w)
    dgrad  dx  = d/dx sum(conv1x1(x, w) * g)     (isolated via jax.grad)
    wgrad  dw  = d/dw sum(conv1x1(x, w) * g)     (the worst measured pass)
    wgrad_fused  Pallas: wgrad + per-channel gout sum fused in the K
                 stream; XLA: wgrad conv + the separate reduction XLA
                 emits for the bias/BN-beta gradient

Methodology: the pinned compiled-window scheme (RESULTS.md round 4) —
each timed window is ONE dispatch of a lax.scan over ``--steps``
iterations whose carry perturbs the weight by a data-dependent ~0 so no
iteration hoists; median of ``--reps`` windows, spread reported.

Before any timing, every row first COMPILES each Pallas kernel the routed
op uses at that shape (forward, the VJP's dgrad/wgrad, the BN-stats
epilogue, the fused wgrad+dsum) and compares it with XLA's conv and its
``jax.grad`` within bf16 tolerance.  A kernel the compiler rejects is
recorded with the compiler's message, its timing is skipped, and the
script exits non-zero.  ``--steps 0`` stops after that check.

Run:    python benchmark/conv_kernel.py               (TPU, bf16)
        python benchmark/conv_kernel.py --steps 0     (TPU: compile and
                                                       compare only)
        python benchmark/conv_kernel.py --interpret   (CPU correctness
                                                       pass, tiny shapes)
Writes: benchmark/conv_kernel_results.json
"""
from __future__ import annotations

import argparse
import functools
import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import jax                                   # noqa: E402
import jax.numpy as jnp                      # noqa: E402
from jax import lax                          # noqa: E402

from paddle_tpu.ops.pallas_conv import (  # noqa: E402
    _from_pixel_major, _to_pixel_major, pallas_matmul)
# the shared measurement harness (paddle_tpu.tuning.search): warmup
# discard, median of windows, spread — this benchmark is a thin driver
# over it since the autotuner PR
from paddle_tpu.tuning.search import time_windows  # noqa: E402

OUT = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                   "conv_kernel_results.json")
DN = ("NCHW", "OIHW", "NCHW")

# ResNet-50 bs128: every 1x1 shape the routing gate accepts (the
# 64-channel stage-1/2 blocks stay on XLA and are not measured)
SHAPES = [
    # (name, N, C, H, W, M, stride)
    ("c512_m128_hw28", 128, 512, 28, 28, 128, 1),
    ("c128_m512_hw28", 128, 128, 28, 28, 512, 1),
    ("c1024_m256_hw14", 128, 1024, 14, 14, 256, 1),
    ("c256_m1024_hw14", 128, 256, 14, 14, 1024, 1),
    ("c2048_m512_hw7", 128, 2048, 7, 7, 512, 1),
    ("c512_m2048_hw7", 128, 512, 7, 7, 2048, 1),
    ("c1024_m2048_s2_hw14", 128, 1024, 14, 14, 2048, 2),
]
INTERPRET_SHAPES = [("tiny_c128_m256_hw16", 2, 128, 16, 16, 256, 1)]


def _xla_conv(x, w, stride):
    return lax.conv_general_dilated(
        x, w, (stride, stride), [(0, 0), (0, 0)], dimension_numbers=DN)


def _pallas_conv(x, w, stride, interpret):
    from paddle_tpu.ops.pallas_conv import conv2d_1x1
    return conv2d_1x1(x, w, (stride, stride), interpret=interpret)


def _views(x, g, w, stride):
    """The matmul views the Pallas per-pass rows operate on — via the
    kernel module's own layout helpers, so the benchmark times exactly
    the relayouts the shipped path pays (input relayouts here; the
    dgrad row also pays the output relayout + stride scatter below)."""
    xs = x[:, :, ::stride, ::stride] if stride != 1 else x
    xm, dims = _to_pixel_major(xs)
    gm, _ = _to_pixel_major(g)
    return xm, gm, w.reshape(w.shape[0], w.shape[1]), dims


def make_step(impl, pas, stride, interpret):
    """(x, w, g) -> scalar the scan carry chains on; one op per step."""
    if impl == "xla":
        if pas == "fwd":
            def f(x, w, g):
                return jnp.sum(_xla_conv(x, w, stride) * g)
        elif pas == "dgrad":
            def f(x, w, g):
                dx = jax.grad(lambda x_: jnp.sum(
                    _xla_conv(x_, w, stride) * g))(x)
                return jnp.sum(dx * dx[..., :1, :1])
        elif pas == "wgrad":
            def f(x, w, g):
                dw = jax.grad(lambda w_: jnp.sum(
                    _xla_conv(x, w_, stride) * g))(w)
                return jnp.sum(dw * dw[..., :1, :, :])
        else:                                   # wgrad_fused A/B partner:
            def f(x, w, g):                     # wgrad + separate bias sum
                dw = jax.grad(lambda w_: jnp.sum(
                    _xla_conv(x, w_, stride) * g))(w)
                dsum = jnp.sum(g, axis=(0, 2, 3))
                return jnp.sum(dw * dw[..., :1, :, :]) + jnp.sum(dsum)
        return f

    from paddle_tpu.ops.pallas_conv import _mm
    if pas == "fwd":
        def f(x, w, g):
            return jnp.sum(_pallas_conv(x, w, stride, interpret) * g)
    elif pas == "dgrad":
        def f(x, w, g):
            # pay everything the shipped VJP pays: the dot, the
            # pixel-major -> NCHW output relayout, and (stride > 1) the
            # zero-scatter back to the input grid — the XLA row's dx has
            # all three baked into its conv, so omitting them here would
            # bias pallas_speedup upward
            _, gm, wm, dims = _views(x, g, w, stride)
            dxm = pallas_matmul(gm, wm, False, False, 512, 512, 1024,
                                interpret)
            dx = _from_pixel_major(dxm, dims, w.shape[1])
            if stride != 1:
                dx = jnp.zeros(x.shape, x.dtype) \
                    .at[:, :, ::stride, ::stride].set(dx)
            return jnp.sum(dx * dx[..., :1, :1])
    elif pas == "wgrad":
        def f(x, w, g):
            xm, gm, _, _ = _views(x, g, w, stride)
            dw = _mm(gm, xm, True, False, 512, 512, 1024, interpret)
            return jnp.sum(dw * dw[:1])
    else:                                       # wgrad + fused dsum epilogue
        def f(x, w, g):
            xm, gm, _, _ = _views(x, g, w, stride)
            dw, dsum = _mm(gm, xm, True, False, 512, 512, 1024, interpret,
                           a_colsum=True)
            return jnp.sum(dw * dw[:1]) + jnp.sum(dsum)
    return f


BF16_TOL = 2e-2      # max |pallas - xla| over max |xla|


def check_row(x, w, g, stride, interpret):
    """Compile each Pallas kernel at this shape and compare with XLA.
    Returns {kernel: {"ok", "rel_err"} | {"ok": False, "error"}} — an
    exception here is the compiler's verdict on the kernel, so its message
    is the recorded outcome (and fails the script), not a swallowed one."""
    from paddle_tpu.ops.pallas_conv import (conv2d_1x1,
                                            conv2d_1x1_grad_fused,
                                            conv2d_1x1_with_bn_stats)
    s2 = (stride, stride)

    def rel(got, want):
        got = np.asarray(got, np.float32)
        want = np.asarray(want, np.float32)
        assert got.shape == want.shape, (got.shape, want.shape)
        return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))

    def loss(conv):
        return lambda x_, w_: jnp.sum(
            (conv(x_, w_) * g).astype(jnp.float32))

    ref_out = _xla_conv(x, w, stride)
    ref_dx, ref_dw = jax.grad(
        loss(lambda a, b: _xla_conv(a, b, stride)), argnums=(0, 1))(x, w)
    ref32 = ref_out.astype(jnp.float32)
    g32 = g.astype(jnp.float32)

    def k_fwd():
        return {"out": rel(jax.jit(lambda a, b: conv2d_1x1(
            a, b, s2, interpret=interpret))(x, w), ref_out)}

    def k_vjp():
        dx, dw = jax.jit(jax.grad(loss(lambda a, b: conv2d_1x1(
            a, b, s2, interpret=interpret)), argnums=(0, 1)))(x, w)
        return {"dx": rel(dx, ref_dx), "dw": rel(dw, ref_dw)}

    def k_bn_stats():
        out, cs, csq = jax.jit(lambda a, b: conv2d_1x1_with_bn_stats(
            a, b, s2, interpret=interpret))(x, w)
        return {"out": rel(out, ref_out),
                "csum": rel(cs, jnp.sum(ref32, axis=(0, 2, 3))),
                "csumsq": rel(csq, jnp.sum(ref32 * ref32, axis=(0, 2, 3)))}

    def k_grad_fused():
        dx, dw, dsum = jax.jit(lambda a, b, c: conv2d_1x1_grad_fused(
            a, b, c, s2, interpret=interpret))(x, w, g)
        return {"dx": rel(dx, ref_dx), "dw": rel(dw, ref_dw),
                "dsum": rel(dsum, jnp.sum(g32, axis=(0, 2, 3)))}

    out = {}
    for name, fn in (("fwd", k_fwd), ("vjp_dgrad_wgrad", k_vjp),
                     ("fwd_bn_stats", k_bn_stats),
                     ("grad_fused_dsum", k_grad_fused)):
        try:
            errs = fn()
        except Exception as e:            # recorded verbatim, fails the run
            out[name] = {"ok": False,
                         "error": f"{type(e).__name__}: {e}"[:1500]}
            continue
        out[name] = {"ok": max(errs.values()) < BF16_TOL,
                     "rel_err": {k: round(v, 5) for k, v in errs.items()}}
    return out


# which compiled-and-compared kernels each timed pass depends on
_PASS_NEEDS = {"fwd": ("fwd",), "dgrad": ("vjp_dgrad_wgrad",),
               "wgrad": ("vjp_dgrad_wgrad",),
               "wgrad_fused": ("grad_fused_dsum",)}


def run_row(name, N, C, H, W, M, stride, steps, reps, dtype, interpret):
    OH, OW = (H - 1) // stride + 1, (W - 1) // stride + 1
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(N, C, H, W), dtype)
    w = jnp.asarray(rng.randn(M, C, 1, 1) * 0.05, dtype)
    g = jnp.asarray(rng.randn(N, M, OH, OW), dtype)
    P = N * OH * OW
    flops = 2.0 * P * C * M                       # per pass per step
    row = {"shape": name, "P": P, "C": C, "M": M, "stride": stride,
           "steps": steps, "check": check_row(x, w, g, stride, interpret),
           "passes": {}}
    print(json.dumps({"shape": name, "check": row["check"]}), flush=True)
    for pas in ("fwd", "dgrad", "wgrad", "wgrad_fused"):
        if steps == 0 or not all(row["check"][k]["ok"]
                                 for k in _PASS_NEEDS[pas]):
            continue
        times = {}
        for impl in ("xla", "pallas"):
            step = make_step(impl, pas, stride, interpret)

            @functools.partial(jax.jit, static_argnames=("n",))
            def window(x, w, g, n):
                def body(carry, _):
                    xc, wc, gc = carry
                    s = step(xc, wc, gc)
                    # data-dependent ~0 perturbation on EVERY operand so
                    # no pass's op is loop-invariant (dgrad reads only
                    # (w, g), wgrad only (x, g) — perturbing w alone
                    # would let XLA hoist those out of the scan)
                    f = (1.0 - 1e-12 * s)
                    return tuple(t * f.astype(t.dtype) for t in carry), s
                _, ss = lax.scan(body, (x, w, g), None, length=n)
                return ss[-1]

            # engine harness: warmup window pays the compile, timed
            # windows materialize the scalar (the completion barrier)
            tw = time_windows(lambda: float(window(x, w, g, steps)),
                              reps=reps, warmup=1, unit=steps)
            med = tw["seconds"]
            times[impl] = {
                "ms": round(med * 1e3, 3),
                "tflops": round(flops / med / 1e12, 1),
                "spread_pct": tw["spread_pct"]}
        times["pallas_speedup"] = round(
            times["xla"]["ms"] / times["pallas"]["ms"], 3)
        row["passes"][pas] = times
        print(json.dumps({"shape": name, "pass": pas, **times}),
              flush=True)
    return row


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--dtype", default="bfloat16")
    ap.add_argument("--interpret", action="store_true",
                    help="CPU correctness pass on a tiny shape (timings "
                         "meaningless; asserts nothing crashes end-to-end)")
    args = ap.parse_args()
    shapes = INTERPRET_SHAPES if args.interpret else SHAPES
    steps = 2 if args.interpret else args.steps
    reps = 1 if args.interpret else args.reps
    dtype = jnp.dtype(args.dtype)
    dev = jax.devices()[0]
    results = {"device": str(dev), "platform": dev.platform,
               "device_kind": dev.device_kind, "jax": jax.__version__,
               "dtype": str(dtype), "steps": steps, "rows": []}
    for spec in shapes:
        results["rows"].append(
            run_row(*spec, steps=steps, reps=reps, dtype=dtype,
                    interpret=args.interpret))
    if not args.interpret:
        with open(OUT, "w") as f:
            json.dump(results, f, indent=1)
        print(f"wrote {OUT}")
    bad = [(r["shape"], k) for r in results["rows"]
           for k, v in r["check"].items() if not v["ok"]]
    if bad:
        sys.exit(f"conv_kernel: compile/compare failed for {bad}")


if __name__ == "__main__":
    main()

"""HBM stream-bandwidth cross-check (VERDICT r4 'what's weak' #2).

The ResNet-50 roofline in RESULTS.md rests on a ~300 GB/s effective HBM
bandwidth figure that was measured only with jnp elementwise kernels.  If
the part actually streams faster and the jnp kernels are the limiter, the
"2650 img/s is the ceiling" claim is wrong.  This benchmark measures the
same quantity three independent ways:

  1. jnp    — the original method: elementwise copy/axpy lowered by XLA,
              K sequential repeats inside one lax.scan dispatch (carry
              evolves each step so nothing hoists out of the loop).
  2. pallas-grid — a Pallas kernel whose grid pipeline auto-double-buffers
              chunk DMAs HBM->VMEM->HBM around the VPU op.
  3. pallas-dma  — a hand-written double-buffered ``pltpu.make_async_copy``
              stream (explicit semaphores, 2 VMEM slots), the method the
              verdict prescribed; pure DMA, no VPU in the loop for copy.

Traffic accounting: copy moves 2N bytes per pass (read + write), axpy
(z = a*x + y) moves 3N.  Reported GB/s = traffic / median window time.

Run on the real chip (no env overrides):  python benchmark/bandwidth.py
Writes benchmark/bandwidth_results.json and prints a table.
"""
from __future__ import annotations

import functools
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 512                      # f32 row = 2 KB
CHUNK_ROWS = 1024                # chunk = 2 MB (2 slots -> 4 MB VMEM)


# ---------------------------------------------------------------------------
# method 1: jnp elementwise, serialized by an evolving scan carry
# ---------------------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=("k",))
def _jnp_copy_k(x, k):
    # c * 1.0 would fold; 1.0000001 keeps a real read+write per step
    return lax.scan(lambda c, _: (c * jnp.float32(1.0000001), None),
                    x, None, length=k)[0]


@functools.partial(jax.jit, static_argnames=("k",))
def _jnp_axpy_k(x, y, k):
    return lax.scan(lambda c, _: (jnp.float32(1.0000001) * x + c, None),
                    y, None, length=k)[0]


# ---------------------------------------------------------------------------
# method 2: Pallas grid pipeline (automatic double-buffered chunk DMA)
# ---------------------------------------------------------------------------
def _grid_copy(x):
    n = x.shape[0] // CHUNK_ROWS

    def kern(x_ref, o_ref):
        o_ref[...] = x_ref[...] * 1.0000001

    return pl.pallas_call(
        kern,
        grid=(n,),
        in_specs=[pl.BlockSpec((CHUNK_ROWS, LANES), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((CHUNK_ROWS, LANES), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
    )(x)


def _grid_axpy(x, y):
    n = x.shape[0] // CHUNK_ROWS

    def kern(x_ref, y_ref, o_ref):
        o_ref[...] = 1.0000001 * x_ref[...] + y_ref[...]

    return pl.pallas_call(
        kern,
        grid=(n,),
        in_specs=[pl.BlockSpec((CHUNK_ROWS, LANES), lambda i: (i, 0))] * 2,
        out_specs=pl.BlockSpec((CHUNK_ROWS, LANES), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
    )(x, y)


@functools.partial(jax.jit, static_argnames=("k",))
def _grid_copy_k(x, k):
    return lax.scan(lambda c, _: (_grid_copy(c), None), x, None,
                    length=k)[0]


@functools.partial(jax.jit, static_argnames=("k",))
def _grid_axpy_k(x, y, k):
    return lax.scan(lambda c, _: (_grid_axpy(x, c), None), y, None,
                    length=k)[0]


# ---------------------------------------------------------------------------
# method 3: hand-written double-buffered make_async_copy stream
# ---------------------------------------------------------------------------
def _dma_copy(x):
    """Pure-DMA copy: chunks stream HBM->VMEM slot->HBM, two slots, input
    DMA for chunk i+1 in flight while chunk i's output DMA drains."""
    n = x.shape[0] // CHUNK_ROWS

    def kern(x_hbm, o_hbm):
        def body(scratch, in_sems, out_sems):
            def in_dma(slot, i):
                return pltpu.make_async_copy(
                    x_hbm.at[pl.ds(i * CHUNK_ROWS, CHUNK_ROWS)],
                    scratch.at[slot], in_sems.at[slot])

            def out_dma(slot, i):
                return pltpu.make_async_copy(
                    scratch.at[slot],
                    o_hbm.at[pl.ds(i * CHUNK_ROWS, CHUNK_ROWS)],
                    out_sems.at[slot])

            in_dma(0, 0).start()

            def loop(i, _):
                slot = i % 2
                nxt = (i + 1) % 2

                # before refilling the other slot, its previous chunk's
                # output DMA must have drained
                @pl.when((i + 1 < n) & (i >= 1))
                def _():
                    out_dma(nxt, i - 1).wait()

                @pl.when(i + 1 < n)
                def _():
                    in_dma(nxt, i + 1).start()

                in_dma(slot, i).wait()
                out_dma(slot, i).start()
                return _

            lax.fori_loop(0, n, loop, None)
            out_dma((n - 1) % 2, n - 1).wait()

            @pl.when(n >= 2)
            def _():
                out_dma(n % 2, n - 2).wait()

        pl.run_scoped(
            body,
            scratch=pltpu.VMEM((2, CHUNK_ROWS, LANES), jnp.float32),
            in_sems=pltpu.SemaphoreType.DMA((2,)),
            out_sems=pltpu.SemaphoreType.DMA((2,)),
        )

    return pl.pallas_call(
        kern,
        in_specs=[pl.BlockSpec(memory_space=pltpu.ANY)],
        out_specs=pl.BlockSpec(memory_space=pltpu.ANY),
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
    )(x)


@functools.partial(jax.jit, static_argnames=("k",))
def _dma_copy_k(x, k):
    return lax.scan(lambda c, _: (_dma_copy(c), None), x, None,
                    length=k)[0]


# ---------------------------------------------------------------------------
def _time_fn(fn, *args, k, traffic_bytes, windows=5):
    # block_until_ready() returns only after the device drains (checked
    # on the chip by chip_smoke.py's env leg), so it IS the window barrier
    fn(*args, k=k).block_until_ready()       # compile + warm
    times = []
    for _ in range(windows):
        t0 = time.perf_counter()
        fn(*args, k=k).block_until_ready()
        times.append(time.perf_counter() - t0)
    med = float(np.median(times))
    return {"gbps": traffic_bytes * k / med / 1e9, "window_s": med,
            "spread_pct": 100.0 * (max(times) - min(times)) / med}


def main():
    results = {"device": str(jax.devices()[0]),
               "chunk_mb": CHUNK_ROWS * LANES * 4 / 2**20, "rows": []}
    sizes_mb = [128, 512, 1024, 2048]
    for mb in sizes_mb:
        rows = mb * 2**20 // (LANES * 4)
        rows -= rows % CHUNK_ROWS
        nbytes = rows * LANES * 4
        # window >= ~2 s at an assumed 300 GB/s, so the sub-millisecond
        # dispatch round trip stays a negligible fraction of it
        k = min(4000, max(4, int(2.0 * 300e9 / (2 * nbytes))))
        key = jax.random.PRNGKey(0)
        x = jax.random.normal(key, (rows, LANES), jnp.float32)
        y = jax.random.normal(jax.random.PRNGKey(1), (rows, LANES),
                              jnp.float32)

        row = {"size_mb": nbytes / 2**20, "k": k}
        row["jnp_copy"] = _time_fn(_jnp_copy_k, x, k=k,
                                   traffic_bytes=2 * nbytes)
        row["jnp_axpy"] = _time_fn(_jnp_axpy_k, x, y, k=k,
                                   traffic_bytes=3 * nbytes)
        if jax.default_backend() == "tpu":
            row["pallas_grid_copy"] = _time_fn(_grid_copy_k, x, k=k,
                                               traffic_bytes=2 * nbytes)
            row["pallas_grid_axpy"] = _time_fn(_grid_axpy_k, x, y, k=k,
                                               traffic_bytes=3 * nbytes)
            row["pallas_dma_copy"] = _time_fn(_dma_copy_k, x, k=k,
                                              traffic_bytes=2 * nbytes)
        results["rows"].append(row)
        del x, y
        print(json.dumps(row))

    out_path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "bandwidth_results.json")
    with open(out_path, "w") as f:
        json.dump(results, f, indent=1)
    print(f"\nwrote {out_path}")
    # summary table
    print(f"{'MB':>6} " + " ".join(f"{m:>16}" for m in
          ("jnp_copy", "jnp_axpy", "grid_copy", "grid_axpy", "dma_copy")))
    for r in results["rows"]:
        vals = [r.get(m, {}).get("gbps") for m in
                ("jnp_copy", "jnp_axpy", "pallas_grid_copy",
                 "pallas_grid_axpy", "pallas_dma_copy")]
        print(f"{r['size_mb']:>6.0f} " + " ".join(
            f"{v:>14.1f}GB" if v else f"{'-':>16}" for v in vals))


if __name__ == "__main__":
    main()

"""Built-in measurement targets for ``python -m paddle_tpu tune`` and
``benchmark/autotune.py``.

A *target* binds a registered tunable to a concrete, self-contained
workload whose one-window runtime ``measure(config)`` the search engine
can time — the subsystem's representative hot loop, sized so a full grid
finishes in minutes on a CPU container (``smoke=True`` shrinks it to
seconds for path checks).

Every builder constructs its fixture ONCE (model, synthetic data) and
returns a closure measuring one window: per-config compile costs land in
the engine's warmup-discard windows, exactly like the committed
benchmarks.
"""
from __future__ import annotations

import time
from typing import Callable, Dict

import numpy as np

__all__ = ["TARGETS", "build_target", "target_names"]


def _target_run_pipelined(smoke: bool) -> Callable[[dict], None]:
    """Pipelined-dispatch chunking on a dispatch-overhead-bound workload:
    a small MLP whose per-step device time is tiny, so steps_per_dispatch
    (host dispatches amortized per compiled scan) and prefetch_depth
    (staging overlap) are the binding knobs — the regime PR 2 measured
    CPU headroom in."""
    import paddle_tpu as pt
    from paddle_tpu import layers

    pt.core.reset_default_programs()
    pt.core.reset_global_scope()
    pt.unique_name.reset()
    x = layers.data("x", shape=[64], dtype="float32")
    y = layers.data("y", shape=[1], dtype="int64")
    h = layers.fc(x, size=64, act="relu")
    pred = layers.fc(h, size=8, act="softmax")
    loss = layers.mean(layers.cross_entropy(pred, y))
    exe = pt.Executor()
    exe.run(pt.default_startup_program(), feed={}, fetch_list=[])
    program = pt.default_main_program()

    rng = np.random.RandomState(0)
    n = 8 if smoke else 48
    feeds = [{"x": rng.rand(32, 64).astype(np.float32),
              "y": rng.randint(0, 8, (32, 1))} for _ in range(n)]

    def measure(cfg: dict):
        outs = list(exe.run_pipelined(
            iter(feeds), program, fetch_list=[loss], is_test=True,
            steps_per_dispatch=cfg["steps_per_dispatch"],
            prefetch_depth=cfg["prefetch_depth"]))
        # materialized numpy fetches ARE the completion barrier
        assert len(outs) == n
    return measure


def _target_reader_prefetch(smoke: bool) -> Callable[[dict], None]:
    """Reader-engine worker/buffer sizing on genuine decode work (string
    parsing, the PR 2 CTR recipe shape) with a consumer that also costs
    host time — the overlap the workers exist to buy."""
    rng = np.random.RandomState(0)
    n = 128 if smoke else 2048
    lines = ["%d," % rng.randint(0, 2)
             + " ".join("%d" % v for v in rng.randint(0, 65536, 13))
             for _ in range(n)]

    def decode(line):
        lab, _, dense_s = line.partition(",")
        return np.array([np.log1p(float(t)) for t in dense_s.split()],
                        np.float32), np.float32(int(lab))

    def reader():
        return iter(lines)

    sink = np.zeros(13, np.float32)

    def measure(cfg: dict):
        from ..reader.pipeline import prefetch
        src = prefetch(reader, buffer_size=cfg["buffer_size"],
                       num_workers=cfg["num_workers"], mapper=decode)
        acc = sink.copy()
        for dense, _lab in src():
            acc += dense            # consumer-side host work (overlap target)
        assert acc.shape == (13,)
    return measure


def _target_serving_batcher(smoke: bool) -> Callable[[dict], None]:
    """Batcher coalescing policy under closed-loop concurrent load on a
    live-program model: max_batch/max_wait_ms trade per-dispatch
    amortization against batch-fill waiting — the knob pair PR 8's
    capacity probe showed CPU headroom on."""
    import threading

    import paddle_tpu as pt
    from paddle_tpu import layers
    from ..serving.model import Model

    pt.core.reset_default_programs()
    pt.core.reset_global_scope()
    pt.unique_name.reset()
    x = layers.data("x", shape=[32], dtype="float32")
    h = layers.fc(x, size=32, act="relu")
    pred = layers.fc(h, size=4, act="softmax")
    exe = pt.Executor()
    exe.run(pt.default_startup_program(), feed={}, fetch_list=[])
    model = Model.from_program(
        exe, pt.default_main_program(), fetch_list=[pred], name="tune-mlp",
        example={"x": np.zeros(32, np.float32)})

    rng = np.random.RandomState(0)
    n_requests = 24 if smoke else 240
    clients = 4 if smoke else 8
    examples = [{"x": rng.rand(32).astype(np.float32)} for _ in range(16)]

    def measure(cfg: dict):
        from ..serving.server import Server
        srv = Server(max_batch=cfg["max_batch"],
                     max_wait_ms=cfg["max_wait_ms"],
                     deadline_ms=None, queue_capacity=None,
                     warmup=True)
        srv.add_model(model)
        srv.start()
        try:
            errors = []
            per_client = n_requests // clients

            def client(ci):
                try:
                    for i in range(per_client):
                        srv.infer(examples[(ci + i) % len(examples)],
                                  timeout=60.0)
                except Exception as e:      # noqa: BLE001 — reported below
                    errors.append(e)

            threads = [threading.Thread(target=client, args=(c,),
                                        name=f"pt-tune-client-{c}")
                       for c in range(clients)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            if errors:
                raise errors[0]
        finally:
            srv.shutdown(drain=True, timeout=30.0)
    return measure


def _sparse_fixture(smoke: bool, **session_kw):
    """Shared sparse-target fixture: a one-table sparse program, a zipf
    feed list, and a session factory (fresh session per config so knob
    changes take effect; the TABLE persists so only the first config
    pays cold-row init)."""
    import paddle_tpu as pt
    from paddle_tpu import layers
    from ..sparse import SparseSession, SparseTable

    pt.core.reset_default_programs()
    pt.core.reset_global_scope()
    pt.unique_name.reset()
    ids = layers.data("ids", shape=[1], dtype="int64")
    label = layers.data("label", shape=[1], dtype="float32")
    emb = layers.embedding(ids, size=[20_000, 16], sparse=True,
                           name="tune_tbl")
    fc = layers.fc(emb, size=16, act="relu")
    loss = layers.mean(layers.square(layers.fc(fc, size=1) - label))
    pt.optimizer.SGD(learning_rate=0.05).minimize(loss)
    program = pt.default_main_program()
    table = SparseTable("tune_tbl", 20_000, 16, num_shards=4, seed=1,
                        learning_rate=0.05)

    rng = np.random.RandomState(0)
    B = 64 if smoke else 256
    n = 6 if smoke else 32
    draws = rng.zipf(1.3, size=(n, B, 1)).astype(np.int64)
    feeds = [{"ids": (draws[i] - 1) % 20_000,
              "label": rng.rand(B, 1).astype(np.float32)}
             for i in range(n)]

    def make_session(**kw):
        merged = dict(session_kw)
        merged.update(kw)
        s = SparseSession(table, bucket_floor=B, **merged)
        s.bind(program)
        return s
    return program, table, feeds, make_session


def _target_sparse_hot_rows(smoke: bool) -> Callable[[dict], None]:
    """Hot-rows LRU capacity on serving-style read-only zipf traffic —
    the cache-first pull loop the capacity knob bounds."""
    _, _, feeds, make_session = _sparse_fixture(smoke)

    def measure(cfg: dict):
        sess = make_session(cache_rows=cfg["cache_rows"])
        for f in feeds:
            sess.prepare_feed(f, is_test=True)
    return measure


def _target_sparse_prefetch(smoke: bool) -> Callable[[dict], None]:
    """Pull-ahead depth on a REAL training loop (pull -> dispatch ->
    push): the overlap only pays when the host has parallelism to
    spare, which is exactly what the paired gate decides."""
    import paddle_tpu as pt

    program, _, feeds, make_session = _sparse_fixture(smoke)
    exe = pt.Executor()
    exe.run(pt.default_startup_program(), feed={}, fetch_list=[])
    loss_name = [op.output("Out")[0] for b in program.blocks
                 for op in b.ops if op.type == "mean"][-1]

    def measure(cfg: dict):
        sess = make_session(prefetch_depth=cfg["depth"])
        fetch = [loss_name] + sess.grad_fetch_list
        feed_it = sess.prefetch_feeds(iter(feeds))
        try:
            for feed in feed_it:
                out = exe.run(program, feed=feed, fetch_list=fetch)
                sess.complete(out[1:])
        finally:
            feed_it.close()
        sess.flush()
    return measure


def _target_sparse_push_flush(smoke: bool) -> Callable[[dict], None]:
    """Async-push drain size on a push-only loop (prepare + complete,
    no dispatch): isolates the worker wakeup/lock amortization the
    knob exists for."""
    _, table, feeds, make_session = _sparse_fixture(smoke)
    rng = np.random.RandomState(1)
    grads = {}

    def measure(cfg: dict):
        sess = make_session(async_push=8,
                            push_flush_batch=cfg["batch"])
        for f in feeds:
            prepared = sess.prepare_feed(f)
            shape = prepared["tune_tbl@ROWS"].shape
            if shape not in grads:
                grads[shape] = rng.randn(*shape).astype(np.float32)
            sess.complete([grads[shape]])
        sess.flush()
    return measure


def _target_decode_slots(smoke: bool) -> Callable[[dict], None]:
    """Decode slot-pool sizing under closed-loop generate load: slots is
    the compiled decode batch (per-step amortization vs padded compute
    at partial occupancy), step_wait_ms the idle-pool poll — the
    continuous-batching knob pair benchmark/decode.py measured."""
    import threading

    from ..serving.decode import DecodeEngine, DecodeRuntime

    V = 64
    rng = np.random.RandomState(0)
    n_requests = 8 if smoke else 64
    clients = 2 if smoke else 4
    prompts = [[int(t) for t in rng.randint(1, V, rng.randint(3, 9))]
               for _ in range(16)]
    max_news = [int(rng.randint(4, 17)) for _ in range(16)]
    # one engine per slot count, built on first use — rebuilding per
    # config would make the A/B pay compile inside timed windows
    engines: Dict[int, DecodeEngine] = {}

    def measure(cfg: dict):
        s = int(cfg["slots"])
        if s not in engines:
            engines[s] = DecodeEngine(
                vocab_size=V, hidden_dim=32, n_layers=1, slots=s,
                max_len=32, seed=0, name=f"tune-dec{s}")
            rt0 = DecodeRuntime(engines[s], step_wait_ms=1.0)
            rt0.start(warmup=True)
            rt0.shutdown()
        rt = DecodeRuntime(engines[s],
                           step_wait_ms=cfg["step_wait_ms"])
        rt.start(warmup=False)
        try:
            errors = []
            per_client = n_requests // clients

            def client(ci):
                try:
                    for i in range(per_client):
                        j = (ci * per_client + i) % len(prompts)
                        rt.submit(prompts[j], max_news[j]).result(60.0)
                except Exception as e:  # noqa: BLE001 — reported below
                    errors.append(e)

            threads = [threading.Thread(target=client, args=(c,),
                                        name=f"pt-tune-dec-{c}")
                       for c in range(clients)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            if errors:
                raise errors[0]
        finally:
            rt.shutdown(drain=True, timeout=30.0)
    return measure


TARGETS: Dict[str, Callable[[bool], Callable[[dict], None]]] = {
    "executor/run_pipelined": _target_run_pipelined,
    "reader/prefetch": _target_reader_prefetch,
    "serving/batcher": _target_serving_batcher,
    "serving/decode_slots": _target_decode_slots,
    "sparse/hot_rows": _target_sparse_hot_rows,
    "sparse/prefetch": _target_sparse_prefetch,
    "sparse/push_flush": _target_sparse_push_flush,
}


#: target name -> module whose import registers the tunable (lazily
#: imported subsystems: serving, the sparse parameter server)
_REGISTERING_MODULE = {
    "serving/batcher": "paddle_tpu.serving.server",
    "serving/decode_slots": "paddle_tpu.serving.decode",
    "sparse/hot_rows": "paddle_tpu.sparse.session",
    "sparse/prefetch": "paddle_tpu.sparse.session",
    "sparse/push_flush": "paddle_tpu.sparse.session",
}


def ensure_registered(name: str):
    """Import the subsystem that declares ``name`` (no-op for tunables
    registered by the core import)."""
    mod = _REGISTERING_MODULE.get(name)
    if mod is not None:
        import importlib
        importlib.import_module(mod)


def target_names():
    return sorted(TARGETS)


def build_target(name: str, smoke: bool = False) -> Callable[[dict], None]:
    """Build the measurement closure for a registered target (importing
    whatever subsystem registers the tunable, e.g. serving)."""
    try:
        builder = TARGETS[name]
    except KeyError:
        raise KeyError(f"no built-in tune target for {name!r}; "
                       f"available: {target_names()}") from None
    t0 = time.perf_counter()
    measure = builder(smoke)
    build_s = time.perf_counter() - t0
    measure.build_seconds = round(build_s, 3)
    return measure

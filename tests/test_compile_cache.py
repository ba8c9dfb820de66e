"""Compile-cache subsystem (core/compile_cache.py): fingerprint-keyed
executor caching, retrace detection, LRU/weakref eviction, the one
persistent-cache directory (JAX's own compilation cache), AOT
``Executor.compile`` and ``Trainer.train(warmup=...)``.

The retrace contract under test: ONE jit trace per (program content, feed
signature, executor config) — repeated ``run``/``run_steps``/
``run_pipelined`` calls must never re-pay trace/lower/compile, while any
fingerprint ingredient changing (program mutation, feed dtype, mesh, amp,
compiler options) must cost exactly one new trace.
"""
import gc
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import layers
from paddle_tpu.core import compile_cache
from paddle_tpu.core.compile_cache import (ExecCache, RetraceError,
                                           retrace_guard)
from paddle_tpu.core.program import Program, program_guard


@pytest.fixture(autouse=True)
def _fresh_stats():
    """Per-test telemetry isolation."""
    compile_cache.stats().reset()
    yield
    compile_cache.stats().reset()


def _build_net(rng, seed=0):
    """Small classifier; returns (loss, feed)."""
    pt.default_main_program().random_seed = seed
    x = layers.data("x", shape=[4], dtype="float32")
    y = layers.data("y", shape=[1], dtype="int64")
    pred = layers.fc(x, size=3, act="softmax")
    loss = layers.mean(layers.cross_entropy(pred, y))
    pt.optimizer.SGD(0.1).minimize(loss)
    feed = {"x": rng.rand(8, 4).astype("float32"),
            "y": rng.randint(0, 3, (8, 1))}
    return loss, feed


def _traces():
    return compile_cache.stats().snapshot().get("traces", 0)


# ---------------------------------------------------------------------------
# retrace detector
# ---------------------------------------------------------------------------
def test_exactly_one_trace_per_signature(rng):
    loss, feed = _build_net(rng)
    exe = pt.Executor()
    with retrace_guard():
        exe.run(pt.default_startup_program(), feed={}, fetch_list=[])
        for _ in range(4):
            exe.run(feed=feed, fetch_list=[loss])
        for _ in range(2):
            exe.run_steps(3, feed=feed, fetch_list=[loss])
    # startup + run variant + run_steps variant
    assert _traces() == 3
    compile_cache.stats().assert_no_retrace()


def test_exactly_one_trace_run_pipelined(rng):
    loss, feed = _build_net(rng)
    exe = pt.Executor()
    exe.run(pt.default_startup_program(), feed={}, fetch_list=[])
    t0 = _traces()

    def feed_iter():
        for _ in range(10):
            yield dict(feed)

    with retrace_guard():
        outs = list(exe.run_pipelined(feed_iter(), fetch_list=[loss],
                                      steps_per_dispatch=4))
        outs += list(exe.run_pipelined(feed_iter(), fetch_list=[loss],
                                       steps_per_dispatch=4))
    assert len(outs) == 20
    # one scan variant + one per-step tail variant, traced once EACH
    # across BOTH pipelined sweeps
    assert _traces() - t0 == 2
    compile_cache.stats().assert_no_retrace()


def test_one_new_trace_on_program_mutation(rng):
    loss, feed = _build_net(rng)
    exe = pt.Executor()
    exe.run(pt.default_startup_program(), feed={}, fetch_list=[])
    exe.run(feed=feed, fetch_list=[loss])
    t0 = _traces()
    layers.mean(loss)                       # version bump, content change
    exe.run(feed=feed, fetch_list=[loss])
    exe.run(feed=feed, fetch_list=[loss])
    assert _traces() - t0 == 1


def test_one_new_trace_on_feed_dtype_change(rng):
    loss, feed = _build_net(rng)
    exe = pt.Executor()
    exe.run(pt.default_startup_program(), feed={}, fetch_list=[])
    exe.run(feed=feed, fetch_list=[loss])
    t0 = _traces()
    # "y" declared int64 is dtype-coerced by run(); vary the UNDECLARED
    # feed precision instead: shape change on x is a new signature
    feed2 = dict(feed, x=feed["x"][:4])
    feed2["y"] = feed["y"][:4]
    exe.run(feed=feed2, fetch_list=[loss])
    exe.run(feed=feed2, fetch_list=[loss])
    assert _traces() - t0 == 1


def test_retrace_guard_fires_on_cache_clear(rng):
    loss, feed = _build_net(rng)
    exe = pt.Executor()
    exe.run(pt.default_startup_program(), feed={}, fetch_list=[])
    with pytest.raises(RetraceError):
        with retrace_guard():
            exe.run(feed=feed, fetch_list=[loss])
            exe._cache.clear()              # force the pathology
            exe.run(feed=feed, fetch_list=[loss])


# ---------------------------------------------------------------------------
# fingerprint ingredients
# ---------------------------------------------------------------------------
def test_fingerprint_invalidation_matrix(rng):
    """Program mutation, feed dtype, amp, compiler options and mesh each
    change the signature; a no-op rebuild does not."""
    from paddle_tpu.parallel import MeshConfig, ShardedExecutor, make_mesh

    loss, feed = _build_net(rng)
    prog = pt.default_main_program()
    exe = pt.Executor()

    def sig(e, feeds=feed, p=None):
        import jax
        # mirror run()'s feed normalization: declared dtypes are coerced
        # BEFORE the signature is computed
        p = p or prog
        gb = p.global_block()
        fa = {}
        for k, v in feeds.items():
            arr = np.asarray(v)
            if gb.has_var(k):
                want = jax.dtypes.canonicalize_dtype(gb.var(k).dtype)
                if arr.dtype != want:
                    arr = arr.astype(want)
            fa[k] = arr
        return e._entry_sig(p, fa, [loss.name], [], False)

    base = sig(exe)
    assert sig(exe) == base                               # stable
    assert sig(pt.Executor()) == base                     # executor-independent
    assert sig(pt.Executor(amp=True)) != base
    assert sig(pt.Executor(check_nan_inf=True)) != base
    assert sig(pt.Executor(compute_dtype="float64")) != base
    assert sig(pt.Executor(
        compiler_options={"xla_cpu_enable_fast_math": True})) != base

    f32 = dict(feed, x=feed["x"].astype("float64"))
    # x declared float32: coerced, same signature; an UNdeclared feed
    # keeps its dtype and must differ
    assert sig(exe, feeds=f32) == base
    extra = dict(feed, z=np.zeros(3, "int32"))
    assert sig(exe, feeds=extra) != base
    assert sig(exe, feeds=dict(
        feed, z=np.zeros(3, "int64"))) != sig(exe, feeds=extra)

    layers.mean(loss)                                     # content change
    assert sig(exe) != base

    m8 = make_mesh(MeshConfig(dp=8))
    m4 = make_mesh(MeshConfig(dp=4), devices=__import__("jax").devices()[:4])
    s8, s4 = ShardedExecutor(mesh=m8), ShardedExecutor(mesh=m4)
    assert sig(s8) != sig(exe)                            # mesh folded in
    assert sig(s8) != sig(s4)                             # mesh shape/devices
    assert sig(ShardedExecutor(
        mesh=m8, param_specs={"w": ("dp",)})) != sig(s8)  # specs folded in


def test_content_identical_programs_share_entry(rng):
    """prune().clone(for_test=True) inference slices built per call (the
    trainer.test pattern) hit ONE cache entry instead of recompiling."""
    loss, feed = _build_net(rng)
    exe = pt.Executor()
    exe.run(pt.default_startup_program(), feed={}, fetch_list=[])
    main = pt.default_main_program()
    t0 = _traces()
    with retrace_guard():
        for _ in range(3):
            test_prog = main.prune([loss]).clone(for_test=True)
            exe.run(test_prog, feed=feed, fetch_list=[loss], is_test=True)
    assert _traces() - t0 == 1


def test_shared_entry_retargets_to_live_client(rng):
    """A shared entry's step fn must not depend on its CREATOR program
    staying alive: when a content-identical client hits the entry, the
    fn's program weakref cell retargets to the client, so a later
    re-trace (lazy-jit fallback) uses the live
    program instead of raising."""
    loss, feed = _build_net(rng)
    exe = pt.Executor()
    exe.run(pt.default_startup_program(), feed={}, fetch_list=[])
    main = pt.default_main_program()
    first = main.prune([loss]).clone(for_test=True)
    exe.run(first, feed=feed, fetch_list=[loss], is_test=True)
    second = main.prune([loss]).clone(for_test=True)
    exe.run(second, feed=feed, fetch_list=[loss], is_test=True)
    del first
    gc.collect()
    (entry,) = [e for e in exe._cache._od.values()
                if any(r() is second for r in e.prog_refs)]
    assert not entry.dead()
    cell = entry._prog_cell()
    assert cell is not None and cell[0]() is second


def test_clone_and_prune_bump_version(rng):
    loss, _ = _build_net(rng)
    main = pt.default_main_program()
    d0 = main.content_digest()
    pruned = main.prune([loss])
    assert pruned.content_digest() != d0       # ops changed, digest follows
    cloned = main.clone(for_test=True)
    assert cloned.version > main.version
    assert main.content_digest() == d0         # original untouched
    main.random_seed += 1                      # mutates without a bump
    assert main.content_digest() != d0         # digest cache keyed on seed


# ---------------------------------------------------------------------------
# eviction: LRU bound + dead-program sweeping
# ---------------------------------------------------------------------------
def test_lru_bound_and_eviction_counter(rng):
    loss, feed = _build_net(rng)
    exe = pt.Executor()
    exe._cache = ExecCache(max_entries=2)
    exe.run(pt.default_startup_program(), feed={}, fetch_list=[])
    for n in (8, 6, 4, 2):                     # distinct feed signatures
        exe.run(feed={k: v[:n] for k, v in feed.items()},
                fetch_list=[loss])
    assert len(exe._cache) == 2
    assert exe._cache.evictions >= 3           # startup + older variants
    assert compile_cache.stats().snapshot()["evictions"] >= 3


def test_dead_program_entries_swept(rng):
    exe = pt.Executor()

    def one_shot(i):
        with program_guard(Program(), Program()):
            x = layers.data("x", shape=[4], dtype="float32")
            out = layers.fc(x, size=2 + i)
            prog = pt.default_main_program()
            exe.run(pt.default_startup_program(), feed={}, fetch_list=[])
            exe.run(prog, feed={"x": np.ones((2, 4), "float32")},
                    fetch_list=[out], is_test=True)

    one_shot(0)
    n_live = len(exe._cache)
    assert n_live >= 1
    gc.collect()                               # programs now unreachable
    exe._cache.sweep()
    assert len(exe._cache) == 0
    assert exe._cache.evictions >= n_live
    # sweeping also happens implicitly on the next put
    one_shot(1)
    assert len(exe._cache) <= 4


def test_state_keys_cache_swept(rng):
    """Dead (scope, keys_version) pairs no longer accumulate unboundedly."""
    from paddle_tpu.core.executor import _STATE_KEYS_CACHE_MAX
    loss, feed = _build_net(rng)
    exe = pt.Executor()
    prog = pt.default_main_program()
    for _ in range(_STATE_KEYS_CACHE_MAX + 10):
        sc = pt.core.Scope()
        with pt.core.scope_guard(sc):
            exe.run(pt.default_startup_program(), feed={}, fetch_list=[])
            exe.run(prog, feed=feed, fetch_list=[loss], scope=sc)
        del sc
        gc.collect()
    entries = prog._state_keys_cache["entries"]
    assert len(entries) <= _STATE_KEYS_CACHE_MAX + 1
    assert compile_cache.stats().snapshot().get(
        "state_keys_evictions", 0) > 0


# ---------------------------------------------------------------------------
# AOT: Executor.compile / CompiledProgram / Trainer warmup
# ---------------------------------------------------------------------------
def test_executor_compile_then_run_no_retrace(rng):
    loss, feed = _build_net(rng)
    exe = pt.Executor()
    exe.run(pt.default_startup_program(), feed={}, fetch_list=[])
    cp = exe.compile(feed=feed, fetch_list=[loss])
    assert cp.compile_times.get("compile_s", 0) > 0
    t0 = _traces()
    with retrace_guard():
        (v1,) = exe.run(feed=feed, fetch_list=[loss])
        (v2,) = cp.run(feed=feed)
    assert _traces() == t0                     # AOT paid the trace already
    assert np.isfinite(v1) and np.isfinite(v2)


def test_executor_compile_spec_feed_and_steps(rng):
    """(shape, dtype) specs compile the same variant concrete feeds hit;
    num_steps compiles the scan variant."""
    loss, feed = _build_net(rng)
    exe = pt.Executor()
    exe.run(pt.default_startup_program(), feed={}, fetch_list=[])
    exe.compile(feed={"x": ((8, 4), "float32"), "y": ((8, 1), "int64")},
                fetch_list=[loss])
    cp = exe.compile(
        feed={"x": ((4, 8, 4), "float32"), "y": ((4, 8, 1), "int64")},
        fetch_list=[loss], num_steps=4, feeds_stacked=True)
    t0 = _traces()
    with retrace_guard():
        exe.run(feed=feed, fetch_list=[loss])
        from paddle_tpu.core.executor import stack_feeds
        exe.run_steps(4, feed=stack_feeds([feed] * 4), fetch_list=[loss],
                      feeds_stacked=True)
    assert _traces() == t0
    assert cp.num_steps == 4


def test_trainer_warmup(rng):
    from paddle_tpu import trainer
    x = layers.data("x", shape=[4], dtype="float32")
    y = layers.data("y", shape=[1], dtype="int64")
    pred = layers.fc(x, size=3, act="softmax")
    loss = layers.mean(layers.cross_entropy(pred, y))
    rows = [(rng.rand(4).astype("float32"), int(rng.randint(3)))
            for _ in range(32)]

    def reader():
        for i in range(0, 32, 8):
            yield rows[i:i + 8]

    t = trainer.SGD(loss, update_equation=pt.optimizer.SGD(0.1))
    t.train(reader, num_passes=1, feed_list=[x, y], warmup=True,
            steps_per_dispatch=2)
    t_after_warm_pass = _traces()
    with retrace_guard():                      # second pass: all cached
        t.train(reader, num_passes=1, feed_list=[x, y],
                steps_per_dispatch=2)
    assert _traces() == t_after_warm_pass


def test_sharded_compile_aot(rng):
    from paddle_tpu.parallel import MeshConfig, ShardedExecutor, make_mesh
    loss, feed = _build_net(rng)
    exe = ShardedExecutor(mesh=make_mesh(MeshConfig(dp=8)))
    exe.run(pt.default_startup_program(), feed={}, fetch_list=[])
    exe.compile(feed=feed, fetch_list=[loss])
    t0 = _traces()
    with retrace_guard():
        (v,) = exe.run(feed=feed, fetch_list=[loss])
    assert _traces() == t0
    assert np.isfinite(v)


# ---------------------------------------------------------------------------
# the one persistent-cache directory
# ---------------------------------------------------------------------------
_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# one fresh process: a spy on jax.config.update, a tiny training run, then
# what the resolver said and what JAX ended up using
_CACHE_PROBE = r"""
import json, sys
import jax
updates = []
_orig = jax.config.update
def _spy(name, val):
    updates.append(name)
    return _orig(name, val)
jax.config.update = _spy
import numpy as np
import paddle_tpu as pt
from paddle_tpu import layers
from paddle_tpu.core import compile_cache
x = layers.data("x", shape=[16], dtype="float32")
y = layers.data("y", shape=[1], dtype="int64")
pred = layers.fc(layers.fc(x, size=32, act="relu"), size=4, act="softmax")
loss = layers.mean(layers.cross_entropy(pred, y))
pt.optimizer.SGD(0.1).minimize(loss)
rng = np.random.RandomState(0)
feed = {"x": rng.rand(8, 16).astype("float32"),
        "y": rng.randint(0, 4, (8, 1))}
exe = pt.Executor()
exe.run(pt.default_startup_program(), feed={}, fetch_list=[])
vals = [float(exe.run(feed=feed, fetch_list=[loss])[0]).hex()
        for _ in range(3)]
print(json.dumps({
    "resolved": compile_cache.cache_dir(),
    "jax_dir": jax.config.jax_compilation_cache_dir,
    "dir_updates": updates.count("jax_compilation_cache_dir"),
    "losses": vals,
    "counters": compile_cache.stats().snapshot()}))
"""


def _cache_probe(**env_over):
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "JAX_COMPILATION_CACHE_DIR")}
    env.update(JAX_PLATFORMS="cpu", **env_over)
    r = subprocess.run([sys.executable, "-c", _CACHE_PROBE], cwd=_ROOT,
                       env=env, capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-3000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


def _ptxc_files(*dirs):
    return [os.path.join(dp, f) for d in dirs if os.path.isdir(d)
            for dp, _, fs in os.walk(d) for f in fs if f.startswith("ptxc-")]


def test_cache_dir_from_environment_is_used_untouched(tmp_path):
    """JAX_COMPILATION_CACHE_DIR set: that directory IS the cache, the code
    never re-points jax_compilation_cache_dir, nothing appears in the
    checkout's .jax_cache, and a second fresh process is served from it
    (JAX's own cache-hit event) with bit-identical losses."""
    d = str(tmp_path / "placed_from_outside")
    repo_cache = compile_cache.REPO_CACHE_DIR
    # thresholds lowered through JAX's own env knobs so the tiny program's
    # sub-second compiles land on disk at all
    knobs = {"JAX_COMPILATION_CACHE_DIR": d,
             "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0",
             "JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES": "-1"}
    cold = _cache_probe(**knobs)
    assert cold["resolved"] == d and cold["jax_dir"] == d
    assert cold["dir_updates"] == 0
    assert cold["counters"].get("jax_cache_hits", 0) == 0
    assert os.listdir(d)                      # the cache is in that dir
    warm = _cache_probe(**knobs)
    assert warm["dir_updates"] == 0
    assert warm["counters"]["jax_cache_hits"] > 0
    assert warm["losses"] == cold["losses"]
    # (other workers' tests fill the shared <checkout>/.jax_cache meanwhile:
    # what is asked is that none of THIS cache's entries landed there)
    if os.path.isdir(repo_cache):
        assert not set(os.listdir(d)) & set(os.listdir(repo_cache))
    assert not _ptxc_files(d, repo_cache)


def test_cache_dir_defaults_to_fixed_path_in_checkout():
    """Unset: the one fixed in-repo path — never a temp name, a pid or a
    time — and JAX's cache is pointed there exactly once."""
    got = _cache_probe()
    want = os.path.join(_ROOT, ".jax_cache")
    assert compile_cache.REPO_CACHE_DIR == want
    assert got["resolved"] == want and got["jax_dir"] == want
    assert got["dir_updates"] == 1
    assert not _ptxc_files(want)

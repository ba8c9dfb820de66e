"""Persistent autotuner (paddle_tpu.tuning): registry contracts, search
engine discipline + fault containment, store invalidation matrix, and
the replay acceptance criteria — zero search cost / zero added retraces
on warm replay, byte-identical defaults when untuned.
"""
import json
import os
import time

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import layers
from paddle_tpu.core import registry as core_registry
from paddle_tpu.core.registry import register_tunable
from paddle_tpu.testing import faultinject


@pytest.fixture
def tuning():
    """Import the package (lazily, like a call site) with clean memo and
    injection state on both sides."""
    from paddle_tpu import tuning as t
    t.clear_memo()
    faultinject.clear()
    yield t
    t.clear_memo()
    faultinject.clear()


@pytest.fixture
def knob(tuning):
    """A throwaway registered tunable, removed afterwards so the global
    registry (and the repo-lint live-vs-AST agreement gate) stays
    pristine."""
    name = "test/knob"
    core_registry._TUNABLES.pop(name, None)
    entry = register_tunable(
        name,
        space={"a": (1, 2), "b": (10, 20)},
        default={"a": 1, "b": 10},
        description="test knob")
    yield name, entry
    core_registry._TUNABLES.pop(name, None)


@pytest.fixture
def autotune_env(tmp_path, tuning, monkeypatch):
    """The resolved cache directory (the tuning store lives under it) placed
    from outside at a throwaway path + the autotune flag on, restored
    afterwards."""
    from paddle_tpu import flags
    prev_auto = flags.get_flag("autotune")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    flags.set_flag("autotune", True)
    yield str(tmp_path)
    flags.set_flag("autotune", prev_auto)
    tuning.clear_memo()


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------
def test_register_tunable_validates_declarations(knob):
    name, _ = knob
    with pytest.raises(ValueError, match="registered twice"):
        register_tunable(name, space={"a": (1,)},
                         default={"a": 1})
    for kwargs, match in [
        (dict(space={}, default={}), "empty"),
        (dict(space={"a": (1,)}, default={"a": 1, "b": 2}),
         "default keys"),
        (dict(space={"a": (1, 2)}, default={"a": 3}),
         "not in its axis"),
        (dict(space={"a": (1, 1)}, default={"a": 1}),
         "duplicate values"),
    ]:
        with pytest.raises(ValueError, match=match):
            register_tunable("test/bad", **kwargs)
    with pytest.raises(ValueError, match="not namespaced"):
        register_tunable("flatname", space={"a": (1,)},
                         default={"a": 1})


def test_grid_configs_default_first_and_complete(tuning, knob):
    name, entry = knob
    configs = list(tuning.grid_configs(entry))
    assert configs[0] == {"a": 1, "b": 10}          # default first
    assert len(configs) == 4
    assert len({repr(sorted(c.items())) for c in configs}) == 4


def test_validate_config_reports_schema_drift(tuning, knob):
    _, entry = knob
    assert tuning.validate_config(entry, {"a": 2, "b": 20}) == []
    assert tuning.validate_config(entry, {"a": 2}) \
        == ["missing param 'b'"]
    assert any("not in declared axis" in p for p in
               tuning.validate_config(entry, {"a": 7, "b": 10}))
    assert any("unknown param" in p for p in
               tuning.validate_config(entry, {"a": 1, "b": 10, "z": 0}))


# ---------------------------------------------------------------------------
# Store: roundtrip + the invalidation matrix (every failure mode is a
# silent fall-back to defaults, like the checkpoint corruption tests)
# ---------------------------------------------------------------------------
def test_store_roundtrip_and_merge_subset(tuning, knob, tmp_path):
    name, _ = knob
    base = str(tmp_path)
    path = tuning.save_record(name, {"a": 2, "b": 20}, base=base,
                              speedup=1.5)
    assert os.path.exists(path)
    rec = tuning.load_record(name, base=base)
    assert rec["config"] == {"a": 2, "b": 20}
    assert rec["speedup"] == 1.5
    # tuned merges over the caller's default and only known keys
    assert tuning.tuned(name, {"a": 1, "b": 10}, base=base) \
        == {"a": 2, "b": 20}
    tuning.clear_memo()
    assert tuning.tuned(name, {"a": 1}, base=base) == {"a": 2}


def test_store_save_rejects_foreign_config(tuning, knob, tmp_path):
    name, _ = knob
    with pytest.raises(ValueError, match="declared space"):
        tuning.save_record(name, {"a": 7, "b": 10}, base=str(tmp_path))


def test_tuned_without_record_returns_default_object(tuning, knob,
                                                     tmp_path):
    name, _ = knob
    default = {"a": 1, "b": 10}
    out = tuning.tuned(name, default, base=str(tmp_path))
    assert out is default            # the SAME object, untouched
    # and the negative lookup memoizes: delete the dir, still default
    out2 = tuning.tuned(name, default, base=str(tmp_path))
    assert out2 is default


def test_store_invalidation_matrix(tuning, knob, tmp_path, monkeypatch):
    """jax/framework version bump, topology change, schema-version bump,
    tunable-space edit, and corrupt/truncated/drifted records each fall
    back to defaults WITHOUT error."""
    from paddle_tpu.core import compile_cache
    from paddle_tpu.tuning import store

    name, entry = knob
    base = str(tmp_path)
    default = {"a": 1, "b": 10}
    winner = {"a": 2, "b": 20}
    tuning.save_record(name, winner, base=base)

    def fresh_tuned():
        tuning.clear_memo()
        return tuning.tuned(name, default, base=base)

    assert fresh_tuned() == winner                 # baseline: replays

    # 1. framework/jax version bump -> different environment key
    monkeypatch.setattr(compile_cache, "environment_key",
                        lambda: ("jax-99.0", "9.9.9", "cpu", 8))
    assert fresh_tuned() is default
    monkeypatch.undo()

    # 2. topology change (device kind / count)
    monkeypatch.setattr(store, "topology_key", lambda: ("TPU v5", 256))
    assert fresh_tuned() is default
    monkeypatch.undo()

    # 3. tuning schema-version bump
    monkeypatch.setattr(store, "TUNING_FORMAT", store.TUNING_FORMAT + 1)
    assert fresh_tuned() is default
    monkeypatch.undo()

    # 4. tunable declaration edit (space digest changes)
    old_space = dict(entry["space"])
    entry["space"]["a"] = (1, 2, 3)
    assert fresh_tuned() is default
    entry["space"].update(old_space)
    assert fresh_tuned() == winner                 # restored: replays again

    path = store.record_path(name, base=base)

    # 5. truncated record
    blob = open(path, "rb").read()
    with open(path, "wb") as f:
        f.write(blob[:len(blob) // 2])
    assert fresh_tuned() is default

    # 6. binary garbage
    with open(path, "wb") as f:
        f.write(b"\x00\xff\x13garbage")
    assert fresh_tuned() is default

    # 7. valid JSON, drifted config (value outside the declared space)
    payload = json.loads(blob.decode())
    payload["config"] = {"a": 7, "b": 10}
    with open(path, "w") as f:
        json.dump(payload, f)
    assert fresh_tuned() is default

    # 8. valid JSON, foreign tunable name
    payload = json.loads(blob.decode())
    payload["tunable"] = "other/knob"
    with open(path, "w") as f:
        json.dump(payload, f)
    assert fresh_tuned() is default

    # intact record replays after all that probing
    with open(path, "wb") as f:
        f.write(blob)
    assert fresh_tuned() == winner


# ---------------------------------------------------------------------------
# Search engine
# ---------------------------------------------------------------------------
def _sleep_measure(costs):
    """Deterministic synthetic workload: per-config sleep."""
    def measure(cfg):
        time.sleep(costs[(cfg["a"], cfg["b"])])
    return measure


def test_grid_search_finds_fastest_and_contains_failures(tuning, knob):
    name, _ = knob
    costs = {(1, 10): 0.015, (1, 20): 0.004, (2, 10): 0.015,
             (2, 20): 0.015}

    def measure(cfg):
        if (cfg["a"], cfg["b"]) == (2, 10):
            raise RuntimeError("this config cannot run")
        time.sleep(costs[(cfg["a"], cfg["b"])])

    result = tuning.grid_search(name, measure, reps=2, warmup=0)
    assert result.best == {"a": 1, "b": 20}
    by_status = {}
    for t in result.trials:
        by_status[t.status] = by_status.get(t.status, 0) + 1
    assert by_status == {"ok": 3, "failed": 1}
    failed = [t for t in result.trials if t.status == "failed"][0]
    assert "cannot run" in failed.error


def test_run_trial_soft_timeout_is_contained(tuning, knob):
    name, _ = knob

    def measure(cfg):
        time.sleep(0.05)

    from paddle_tpu.tuning.search import run_trial
    t = run_trial(measure, {"a": 1, "b": 10}, reps=3, warmup=0,
                  trial_timeout_s=0.01)
    assert t.status == "timeout"
    assert t.seconds is None


def test_faultinject_site_fail_and_timeout(tuning, knob):
    """tuning.trial[fail/timeout]: deterministic containment — the search
    records the injected trial and keeps going."""
    name, _ = knob
    faultinject.configure("tuning.trial@1=fail;tuning.trial@2=timeout")
    calls = []

    def measure(cfg):
        calls.append(dict(cfg))

    result = tuning.grid_search(name, measure, reps=1, warmup=0)
    statuses = [t.status for t in result.trials]
    assert statuses[0] == "failed"
    assert statuses[1] == "timeout"
    assert statuses[2:] == ["ok", "ok"]
    assert faultinject.fired("tuning.trial") == 2
    assert result.best is not None                 # search survived


def test_successive_halving_converges(tuning, knob):
    name, _ = knob
    costs = {(1, 10): 0.012, (1, 20): 0.012, (2, 10): 0.003,
             (2, 20): 0.012}
    result = tuning.successive_halving(name, _sleep_measure(costs),
                                       reps=3, warmup=0)
    assert result.best == {"a": 2, "b": 10}
    assert result.algo == "halving"


def test_paired_ab_noise_gate_refuses_flat_and_accepts_real(tuning, knob):
    name, _ = knob

    def flat(cfg):
        time.sleep(0.004)

    v = tuning.paired_ab(flat, {"a": 1, "b": 10}, {"a": 2, "b": 20},
                         pairs=4, warmup=0)
    assert not v["accepted"]
    assert "noise band" in v["refusal_reason"]
    assert len(v["default_windows"]) == len(v["candidate_windows"]) == 4

    def real(cfg):
        time.sleep(0.012 if cfg == {"a": 1, "b": 10} else 0.004)

    v = tuning.paired_ab(real, {"a": 1, "b": 10}, {"a": 2, "b": 20},
                         pairs=4, warmup=0)
    assert v["accepted"]
    assert v["speedup"] > 1.5


def test_tune_persists_winner_and_replays(tuning, knob, tmp_path):
    name, _ = knob
    base = str(tmp_path)
    costs = {(1, 10): 0.015, (1, 20): 0.003, (2, 10): 0.015,
             (2, 20): 0.015}
    doc = tuning.tune(name, _sleep_measure(costs), reps=2, pairs=3,
                      warmup=0, base=base)
    assert doc["status"] == "winner"
    assert doc["winner"] == {"a": 1, "b": 20}
    assert os.path.exists(doc["record_path"])
    assert tuning.tuned(name, {"a": 1, "b": 10}, base=base) \
        == {"a": 1, "b": 20}


class _CountedClock:
    """Stands in for the ``time`` module ``tuning.search`` reads: the clock
    moves only when a measurement says what it cost, so equal
    configurations cost the same by construction, whatever else the
    machine is doing."""

    def __init__(self):
        self.now = 0.0

    def perf_counter(self):
        return self.now

    def sleep(self, seconds):
        self.now += seconds


def test_tune_refusal_persists_nothing(tuning, knob, tmp_path, monkeypatch):
    from paddle_tpu.tuning import search

    name, _ = knob
    base = str(tmp_path)
    clock = _CountedClock()
    monkeypatch.setattr(search, "time", clock)
    # distinct configs, identical cost: any "winner" is jitter
    doc = tuning.tune(name, lambda cfg: clock.sleep(0.004), reps=2,
                      pairs=3, warmup=0, base=base)
    assert doc["status"] in ("noise_gate_refusal", "default_is_best")
    assert doc.get("winner") is None
    assert tuning.list_records(base=base) == []
    if doc["status"] == "noise_gate_refusal":
        # the refusal carries its evidence: raw windows + pair ratios
        assert doc["ab"]["pair_ratios"]
        assert doc["ab"]["default_windows"]


# ---------------------------------------------------------------------------
# Replay acceptance: zero search cost, zero added retraces, byte-identical
# defaults when untuned
# ---------------------------------------------------------------------------
def _tiny_net():
    x = layers.data("x", shape=[8], dtype="float32")
    y = layers.data("y", shape=[1], dtype="int64")
    h = layers.fc(x, size=8, act="relu")
    pred = layers.fc(h, size=3, act="softmax")
    loss = layers.mean(layers.cross_entropy(pred, y))
    pt.optimizer.SGD(learning_rate=0.1).minimize(loss)
    return loss


def _feeds(n, batch=8):
    rng = np.random.RandomState(5)
    return [{"x": rng.rand(batch, 8).astype(np.float32),
             "y": rng.randint(0, 3, (batch, 1))} for _ in range(n)]


def test_untuned_call_sites_resolve_todays_defaults(tuning):
    """With autotune off — and with it on but no record — every tuned
    call site resolves byte-identical to the hand-picked defaults."""
    exe = pt.Executor()                      # autotune defers to the flag
    d = {"steps_per_dispatch": 4, "prefetch_depth": 2}
    assert exe._tuned("executor/run_pipelined", d) is d
    exe_on = pt.Executor(autotune=True)      # on, but no record
    assert exe_on._tuned("executor/run_pipelined", d) == d

    from paddle_tpu.reader.pipeline import _tuned_defaults
    assert _tuned_defaults(None, None) == (8, 1)
    assert _tuned_defaults(3, 2) == (3, 2)   # explicit always wins


def test_run_pipelined_default_resolution_matches_explicit(tuning):
    """run_pipelined() with omitted knobs (autotune off) is bit-identical
    to the explicit (4, 2) call — the defaults went through the tuned()
    seam without changing."""
    feeds = _feeds(6)
    loss = _tiny_net()
    exe = pt.Executor()
    exe.run(pt.default_startup_program(), feed={}, fetch_list=[])
    outs_default = [o[0] for o in exe.run_pipelined(
        iter(feeds), pt.default_main_program(), fetch_list=[loss])]

    pt.core.reset_default_programs()
    pt.core.reset_global_scope()
    pt.unique_name.reset()
    loss2 = _tiny_net()
    exe2 = pt.Executor()
    exe2.run(pt.default_startup_program(), feed={}, fetch_list=[])
    outs_explicit = [o[0] for o in exe2.run_pipelined(
        iter(feeds), pt.default_main_program(), fetch_list=[loss2],
        steps_per_dispatch=4, prefetch_depth=2)]
    assert len(outs_default) == len(outs_explicit) == 6
    for a, b in zip(outs_default, outs_explicit):
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_warm_replay_zero_search_trials_zero_retraces(tuning, knob,
                                                      autotune_env):
    """THE acceptance test: a persisted executor/run_pipelined winner
    replays into the call site with ZERO search trials and ZERO added
    retraces — counter-delta + retrace_guard."""
    from paddle_tpu.core import compile_cache
    from paddle_tpu.observability import registry

    base = autotune_env
    tuning.save_record("executor/run_pipelined",
                       {"steps_per_dispatch": 2, "prefetch_depth": 1},
                       base=base)
    tuning.clear_memo()

    loss = _tiny_net()
    exe = pt.Executor(autotune=True)
    exe.run(pt.default_startup_program(), feed={}, fetch_list=[])
    feeds = _feeds(4)

    trials_before = registry().snapshot()["tuning/trials"]["value"]
    outs = list(exe.run_pipelined(iter(feeds), pt.default_main_program(),
                                  fetch_list=[loss]))
    assert len(outs) == 4
    # the replayed K=2 really drove the dispatch: 4 feeds -> 2 scans
    # (per-dispatch evidence: the K=2 scan variant exists in the cache)
    assert len(exe._cache) >= 1

    # warm pass: same variants, zero new traces, zero search trials
    traces_before = compile_cache.stats().snapshot().get("traces", 0)
    with compile_cache.retrace_guard():
        outs2 = list(exe.run_pipelined(iter(feeds),
                                       pt.default_main_program(),
                                       fetch_list=[loss]))
    assert len(outs2) == 4
    assert compile_cache.stats().snapshot().get("traces", 0) \
        == traces_before
    trials_after = registry().snapshot()["tuning/trials"]["value"]
    assert trials_after == trials_before, \
        "replay must never run search trials"


def test_replay_reaches_every_host_call_site(tuning, knob, autotune_env):
    """Persisted winners are picked up by the serving batcher, the
    reader prefetch defaults, the flash-attention layer attrs, and the
    trainer's pipeline-opt fill."""
    base = autotune_env
    tuning.save_record("executor/run_pipelined",
                       {"steps_per_dispatch": 16, "prefetch_depth": 1},
                       base=base)
    tuning.clear_memo()

    # trainer fills omitted knobs from the winner; explicit keys win
    loss = _tiny_net()
    sgd = pt.trainer.SGD.__new__(pt.trainer.SGD)   # no re-minimize
    sgd.exe = pt.Executor(autotune=True)
    opts = {"buffer_size": 99}
    sgd._fill_tuned_pipeline_opts(opts, steps_per_dispatch=1)
    assert opts["steps_per_dispatch"] == 16
    assert opts["prefetch_depth"] == 1
    assert opts["buffer_size"] == 99               # explicit survived
    assert opts["num_workers"] == 1                # no record: default
    del loss

    # reader prefetch defaults
    from paddle_tpu.core.registry import has_tunable
    assert has_tunable("reader/prefetch")
    tuning.save_record("reader/prefetch",
                       {"num_workers": 2, "buffer_size": 16}, base=base)
    tuning.clear_memo()
    from paddle_tpu.reader.pipeline import _tuned_defaults
    assert _tuned_defaults(None, None) == (16, 2)

    # serving batcher (no server started; constructor-time resolution)
    import paddle_tpu.serving.server as srv_mod
    tuning.save_record("serving/batcher",
                       {"max_batch": 8, "max_wait_ms": 2.0}, base=base)
    tuning.clear_memo()
    s = srv_mod.Server(autotune=True)
    assert (s.max_batch, s.max_wait_s) == (8, 0.002)
    s_off = srv_mod.Server(autotune=False)
    assert (s_off.max_batch, s_off.max_wait_s) == (32, 0.005)
    s_explicit = srv_mod.Server(max_batch=64, autotune=True)
    assert s_explicit.max_batch == 64              # explicit wins


def _store_record_of_a_retired_tunable(tuning, base, name, space, config):
    """Leave in the store a record under ``name``, a tunable this tree no
    longer declares, as a tree that declared it would have written it."""
    core_registry._TUNABLES.pop(name, None)
    register_tunable(name, space=space,
                     default={k: v[0] for k, v in space.items()})
    try:
        tuning.save_record(name, config, base=base)
    finally:
        core_registry._TUNABLES.pop(name, None)
    tuning.clear_memo()


@pytest.mark.parametrize("given", [{}, {"block_q": 512}, {"block_k": 2048}],
                         ids=["omitted", "block_q", "block_k"])
@pytest.mark.parametrize("autotune", ["off", "on", "on_with_old_record"])
def test_flash_attention_blocks_are_1024_unless_given(
        tuning, autotune_env, given, autotune):
    """layers.flash_attention writes 1024 for an omitted block and the
    given value for a given one, whatever the autotune switch and the
    store hold: no tunable picks a kernel's blocks."""
    from paddle_tpu import flags
    flags.set_flag("autotune", autotune != "off")
    if autotune == "on_with_old_record":
        _store_record_of_a_retired_tunable(
            tuning, autotune_env, "pallas/flash_attention",
            {"block_q": (512, 1024, 2048), "block_k": (1024, 2048, 4096)},
            {"block_q": 2048, "block_k": 4096})
    q = layers.data("q", shape=[16, 64], dtype="float32")
    layers.flash_attention(q, q, q, **given)
    op = [o for o in pt.default_main_program().global_block().ops
          if o.type == "flash_attention"][-1]
    assert (op.attrs["block_q"], op.attrs["block_k"]) == (
        given.get("block_q", 1024), given.get("block_k", 1024))


# space_digest of each host tunable as the tree that still declared device
# tunables computed it: stored winners keep replaying
_HOST_TUNABLE_DIGESTS = {
    "executor/run_pipelined": "2007ee5ec8f33b19",
    "reader/prefetch": "a842fd2382bdfff8",
    "serving/batcher": "c26cf6d514a8526b",
    "serving/decode_slots": "2dd372579ee6563b",
    "sparse/hot_rows": "72aa43e024f589bc",
    "sparse/prefetch": "32d8545d3c928733",
    "sparse/push_flush": "a824c4afd0e8df53",
}


@pytest.mark.parametrize("name", sorted(_HOST_TUNABLE_DIGESTS))
def test_host_tunable_space_digest_is_unchanged(tuning, name):
    from paddle_tpu.tuning import targets
    targets.ensure_registered(name)
    assert tuning.space_digest(tuning.get_tunable(name)) \
        == _HOST_TUNABLE_DIGESTS[name]


@pytest.mark.parametrize("sharded", [False, True],
                         ids=["executor", "sharded_one_device"])
def test_autotune_leaves_fingerprint_and_compiler_options_alone(
        tuning, autotune_env, monkeypatch, sharded):
    """Executor(autotune=True) and Executor(autotune=False) compile one
    program to the same fingerprint and hand CachedStep the same
    compiler_options: no tunable reaches the compile."""
    import jax

    from paddle_tpu.core import compile_cache
    from paddle_tpu.parallel import ShardedExecutor

    seen = []
    real = compile_cache.CachedStep

    def spy(fn, fingerprint, **kw):
        seen.append((fingerprint, kw.get("compiler_options")))
        return real(fn, fingerprint, **kw)

    monkeypatch.setattr(compile_cache, "CachedStep", spy)
    loss = _tiny_net()
    feed = _feeds(1)[0]
    for autotune in (False, True):
        if sharded:
            from jax.sharding import Mesh
            exe = ShardedExecutor(
                mesh=Mesh(np.array(jax.devices()[:1]), ("dp",)),
                autotune=autotune)
        else:
            exe = pt.Executor(autotune=autotune)
        exe.run(pt.default_startup_program(), feed={}, fetch_list=[])
        exe.run(pt.default_main_program(), feed=feed, fetch_list=[loss])
    # startup then main, autotune off, then the same two with it on (the
    # second startup finds the scope filled, so only the mains compare)
    assert len(seen) == 4, seen
    assert seen[1] == seen[3]
    assert all(opts == {} for _, opts in seen)


def test_import_paddle_tpu_does_not_load_tuning():
    """Runtime half of the lazy-import contract (static half in
    test_repo_lint): the core import path and an untuned executor run
    never pull paddle_tpu.tuning into sys.modules.  In-process proxy:
    this suite imports tuning in its own fixtures, so assert on the
    DECLARATION side — registering tunables needed no tuning import,
    and core.registry (which owns the declarations AND the shared
    ``resolve_tuned`` replay helper since round 15) only names the
    package inside the opted-in branch of that helper: every
    ``from ..tuning`` in its source is function-local, so importing
    the registry can never load the package."""
    import ast
    import importlib
    reg = importlib.import_module("paddle_tpu.core.registry")
    tree = ast.parse(open(reg.__file__).read())
    for node in tree.body:                   # MODULE level only
        assert not (isinstance(node, ast.ImportFrom)
                    and node.module and "tuning" in node.module)
        assert not (isinstance(node, ast.Import) and any(
            "tuning" in a.name for a in node.names))
    # and an untuned dispatch resolves without the package: the off path
    # short-circuits before any tuning import (`is` pins the
    # byte-identical-when-untuned contract)
    exe = pt.Executor(autotune=False)
    d = {"steps_per_dispatch": 4, "prefetch_depth": 2}
    assert exe._tuned("executor/run_pipelined", d) is d
    from paddle_tpu.core.registry import resolve_tuned
    assert resolve_tuned("executor/run_pipelined", d, False) is d


def test_warmup_aot_compiles_the_tuned_scan_variant(tuning, knob,
                                                    autotune_env):
    """train(pipeline=True, warmup=True, autotune=True) with a persisted
    winner must AOT-compile the WINNER's K — the training loop then
    dispatches with zero traces (warmup compiling the untuned K and the
    loop paying a first-dispatch compile stall was the bug)."""
    from paddle_tpu.core import compile_cache

    base = autotune_env
    tuning.save_record("executor/run_pipelined",
                       {"steps_per_dispatch": 2, "prefetch_depth": 1},
                       base=base)
    tuning.clear_memo()

    x = layers.data("x", shape=[8], dtype="float32")
    y = layers.data("y", shape=[1], dtype="int64")
    pred = layers.fc(x, size=3, act="softmax")
    cost = layers.mean(layers.cross_entropy(pred, y))
    sgd = pt.trainer.SGD(cost, update_equation=pt.optimizer.SGD(
        learning_rate=0.1))

    rng = np.random.RandomState(2)
    rows = [list(zip(rng.rand(8, 8).astype(np.float32),
                     rng.randint(0, 3, (8, 1)))) for _ in range(4)]

    def reader():
        return iter(rows)

    # warmup compiles startup + single-step + the K=2 scan variant; the
    # 4-batch loop (two K=2 scans) must then trace NOTHING new
    sgd.train(reader, num_passes=1, feed_list=[x, y],
              pipeline=True, warmup=True, autotune=True,
              event_handler=lambda e: None)
    # exactly 3 variants exist: startup, single-step, the K=2 scan — a
    # warmup that ignored the winner would have AOT-compiled a FOURTH
    # (the untuned K=8 scan) and the loop would have traced K=2 cold
    assert len(sgd.exe._cache) == 3, \
        f"expected startup+single+K=2 variants, got {len(sgd.exe._cache)}"
    traces_after_first = compile_cache.stats().snapshot().get("traces", 0)
    with compile_cache.retrace_guard():
        sgd.train(reader, num_passes=1, feed_list=[x, y],
                  pipeline=True, autotune=True,
                  event_handler=lambda e: None)
    assert compile_cache.stats().snapshot().get("traces", 0) \
        == traces_after_first


# ---------------------------------------------------------------------------
# CLI + observability surfacing
# ---------------------------------------------------------------------------
def test_tune_cli_smoke_in_process(tuning, capsys):
    from paddle_tpu import cli
    rc = cli.main(["tune", "reader/prefetch", "--smoke", "--budget", "2",
                   "--reps", "1", "--pairs", "2", "--no-save"])
    assert rc == 0
    out = capsys.readouterr().out.strip().splitlines()
    summary = json.loads(out[-1])
    assert summary["tunable"] == "reader/prefetch"
    assert summary["status"] in ("winner", "default_is_best",
                                 "noise_gate_refusal", "no_viable_config")


def test_tune_cli_lists_registry(tuning, capsys):
    from paddle_tpu import cli
    assert cli.main(["tune", "--list"]) == 0
    out = capsys.readouterr().out
    listed = {line for line in out.splitlines()
              if line and not line.startswith(" ")}
    assert listed == set(_HOST_TUNABLE_DIGESTS)


def test_profile_is_not_a_subcommand(capsys):
    """The eager per-op profiler is gone: `paddle_tpu profile` reaches
    the trainer's own parser, which refuses it."""
    from paddle_tpu import cli
    with pytest.raises(SystemExit) as e:
        cli.main(["profile", "prog.json"])
    assert e.value.code == 2


def test_tuning_events_reach_stats_summary(tuning, knob, tmp_path):
    """Search/winner/replay events land in the JSONL log and the stats
    summarizer renders a tuning section."""
    from paddle_tpu import flags
    from paddle_tpu.observability import export

    name, _ = knob
    log = str(tmp_path / "run.jsonl")
    prev = flags.get_flag("metrics_log")
    flags.set_flag("metrics_log", log)
    try:
        costs = {(1, 10): 0.012, (1, 20): 0.003, (2, 10): 0.012,
                 (2, 20): 0.012}
        tuning.tune(name, _sleep_measure(costs), reps=2, pairs=3,
                    warmup=0, base=str(tmp_path))
        tuning.clear_memo()
        tuning.tuned(name, {"a": 1, "b": 10}, base=str(tmp_path))
    finally:
        flags.set_flag("metrics_log", prev)
        export._reset_writer()
    summary = export.summarize_log(log)
    tu = summary["tuning"]
    assert tu["trials"] == 4
    assert tu["winners"] and tu["winners"][0]["config"] \
        == {"a": 1, "b": 20}
    assert tu["replays"] and tu["replays"][0]["tunable"] == name
    rendered = export.render_summary(summary)
    assert "tuning:" in rendered and "winner:" in rendered

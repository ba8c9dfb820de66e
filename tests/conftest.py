"""Test environment: force an 8-virtual-device CPU platform BEFORE jax
imports, so mesh/sharding tests run without TPU hardware (the driver's
dryrun uses the same trick)."""
import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = \
        (flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

jax.config.update("jax_default_matmul_precision", "highest")

import numpy as np  # noqa: E402
import pytest  # noqa: E402


REFERENCE_ROOT = "/root/reference"


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: full benchmark A/Bs (minutes); deselect with -m 'not slow'")
    config.addinivalue_line(
        "markers",
        "timeout(seconds): per-test budget (advisory when pytest-timeout "
        "is absent; chaos subprocess tests ALSO pass hard timeouts to "
        "every subprocess call)")
    config.addinivalue_line(
        "markers",
        "needs_reference: reads config/data files from the reference "
        "checkout at /root/reference; SKIPPED (not failed) when that "
        "mount is absent so pre-existing environment gaps cannot mask "
        "real regressions")
    config.addinivalue_line(
        "markers",
        "needs_multiprocess_collectives: real multi-process collectives "
        "round; SKIPPED on the CPU backend (jax CPU cannot run "
        "cross-process psum) so it runs — and fails loudly if broken — "
        "the first session with a chip/GPU")


def pytest_collection_modifyitems(config, items):
    """Convert known environment gaps into EXPLICIT skips with reasons.

    Before this hook the reference-unmounted v1/cli suites and the
    CPU-collectives round were permanent tier-1 FAILURES (27 at the
    PR 13 seed), which meant every session had to eyeball the failure
    list to tell 'pre-existing' from 'new regression'.  Skips keep the
    signal: a mounted /root/reference (or a chip backend) re-enables
    them automatically."""
    ref_missing = not os.path.isdir(REFERENCE_ROOT)
    # NOTE: this conftest pins JAX_PLATFORMS=cpu unconditionally (line
    # 6), so the env var says nothing about the MACHINE — probe for
    # accelerator device files instead, so a chip/GPU host still runs
    # the collectives round (and surfaces a regression) while
    # CPU-only containers skip it with a reason.
    has_accelerator = any(
        os.path.exists(p) for p in
        ("/dev/accel0", "/dev/accel1", "/dev/vfio/0",
         "/dev/nvidia0", "/dev/nvidiactl"))
    skip_ref = pytest.mark.skip(
        reason=f"{REFERENCE_ROOT} not mounted (reference-dependent "
               f"v1/cli suite)")
    skip_coll = pytest.mark.skip(
        reason="no accelerator on this host and the CPU backend has no "
               "multi-process collectives (runs on chip/GPU sessions)")
    for item in items:
        if ref_missing and item.get_closest_marker("needs_reference"):
            item.add_marker(skip_ref)
        if not has_accelerator and item.get_closest_marker(
                "needs_multiprocess_collectives"):
            item.add_marker(skip_coll)


@pytest.fixture(autouse=True)
def fresh_state():
    """Fresh default programs/scope/name-counters per test."""
    import paddle_tpu as pt
    pt.core.reset_default_programs()
    pt.core.reset_global_scope()
    pt.unique_name.reset()
    yield


@pytest.fixture(autouse=True)
def no_leaked_pipeline_threads():
    """Fail any test that leaks a live input-pipeline worker thread, and
    assert every live framework (``pt-*``) thread carries a prefix
    registered in the frozen ``THREAD_NAME_PREFIXES`` table — the
    runtime twin of the static PT055 rule.

    The reader/executor pipeline engine guarantees its workers die with
    their consumer (paddle_tpu/reader/pipeline.py); this enforces the
    guarantee for every test, with a short grace period for the workers'
    stop-event poll to fire after generator close/GC."""
    yield
    import gc
    import sys
    import threading
    import time

    from paddle_tpu.observability.metrics import THREAD_NAME_PREFIXES
    from paddle_tpu.reader.pipeline import THREAD_NAME_PREFIX

    # PT055's runtime twin: any live thread claiming the framework's
    # pt- namespace must carry a REGISTERED prefix (a new subsystem
    # must add its prefix to the frozen table, not invent one ad hoc)
    registered = tuple(p for p, _help in THREAD_NAME_PREFIXES)
    rogue = [t.name for t in threading.enumerate()
             if t.is_alive() and t.name.startswith("pt-")
             and not any(t.name == p or t.name.startswith(p + "-")
                         for p in registered)]
    assert not rogue, (
        f"live framework thread(s) with unregistered pt- name prefix "
        f"{rogue}; register the prefix in observability.metrics."
        f"THREAD_NAME_PREFIXES")

    # the sparse session's workers (prefetch join-on-close, async-push
    # bounded idle linger) carry their own prefix; only enforce it when
    # the test actually loaded the lazily-imported sparse package
    prefixes = [THREAD_NAME_PREFIX]
    sparse_mod = sys.modules.get("paddle_tpu.sparse.session")
    if sparse_mod is not None:
        prefixes.append(sparse_mod.THREAD_NAME_PREFIX)
    # the checkpoint commit writer has the same bounded-idle-linger
    # contract (distributed/checkpoint.py)
    ckpt_mod = sys.modules.get("paddle_tpu.distributed.checkpoint")
    if ckpt_mod is not None:
        prefixes.append(ckpt_mod.THREAD_NAME_PREFIX)

    def leaked():
        return [t for t in threading.enumerate()
                if t.is_alive()
                and any(t.name.startswith(p) for p in prefixes)]

    if leaked():
        gc.collect()           # close abandoned pipeline generators
        deadline = time.monotonic() + 2.0
        while leaked() and time.monotonic() < deadline:
            time.sleep(0.05)
    threads = leaked()
    assert not threads, (
        f"test leaked live input-pipeline worker threads: "
        f"{[t.name for t in threads]}")


# Threaded suites run with the lockwatch order watchdog ON: locks these
# tests create through the lockwatch factories record the process-wide
# acquisition-order graph, an inversion raises deterministically at the
# acquire site, and any violation swallowed by a broad except still
# fails the test here.  ENABLED is flipped directly (not via env):
# the factories consult it per call, so objects built inside the test
# get watched primitives while other suites keep plain ones.
_LOCKWATCH_SUITES = frozenset({
    "test_serving", "test_serving_chaos", "test_decode",
    "test_http_front", "test_fleet", "test_fleet_chaos",
    "test_input_pipeline", "test_master_service", "test_sparse_trainer",
    "test_checkpoint_delta", "test_checkpoint_sharded", "test_pserver",
    "test_elastic",
})


@pytest.fixture(autouse=True)
def lockwatch_for_threaded_suites(request):
    mod = getattr(request, "module", None)
    name = getattr(mod, "__name__", "").rsplit(".", 1)[-1]
    if name not in _LOCKWATCH_SUITES:
        yield
        return
    from paddle_tpu.testing import lockwatch as lw
    prior = lw.ENABLED
    lw.ENABLED = True
    lw.reset()
    try:
        yield
    finally:
        vs = lw.violations()
        lw.ENABLED = prior
        lw.reset()
    assert not vs, (
        "lockwatch recorded lock-order violation(s) during this test:\n"
        + "\n\n".join(v.report() for v in vs))


@pytest.fixture
def rng():
    return np.random.RandomState(42)

"""In-process loopback tests for the served sparse tier (ISSUE 17).

Real sockets, real frames — but the shard servers run on daemon threads
in THIS interpreter, so the whole file stays tier-1 fast (the
multi-process SIGKILL/SIGTERM chaos lives in test_pserver_chaos.py,
marked slow).  What these pin:

* **remote-vs-in-process bit-identity**: a 2-shard fleet driven through
  :class:`RemoteSparseTable` produces byte-identical rows, Adagrad
  slots, and checkpoint exports to ``SparseTable(num_shards=2)`` — the
  wire tier buys distribution, never drift;
* exactly-once pushes: (cid, seq) dedup on retries, typed spec/wiring
  mismatch refusals, faultinject at ``pserver.rpc`` riding the client's
  retry/reconnect rim;
* chain-backup replication: shard k's acked pushes survive k's death
  via the copy shard k+1 holds, and a relaunched k restores from it;
* :class:`SparseSession` composes with a remote table unchanged.
"""
import threading
import time

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import layers
from paddle_tpu.faults import RetryPolicy, RetriesExhausted
from paddle_tpu.sparse import SparseSession, SparseTable
from paddle_tpu.sparse.client import RemoteSparseTable, RemoteTableError
from paddle_tpu.sparse.pserver import PServer
from paddle_tpu.testing import faultinject

HOST = "127.0.0.1"
# io_timeout short enough that a wedged-peer test fails fast, long
# enough for a loaded CI box
IO_TO = 10.0


@pytest.fixture
def fleet2():
    """A 2-shard in-thread fleet wired as a chain cycle 0 -> 1 -> 0."""
    servers, threads = [], []
    for k in range(2):
        s = PServer(k, 2, host=HOST, io_timeout_s=IO_TO)
        s.start()
        servers.append(s)
    servers[0].backup_addr = (HOST, servers[1].port)
    servers[1].backup_addr = (HOST, servers[0].port)
    for s in servers:
        t = threading.Thread(target=s.serve_forever, daemon=True)
        t.start()
        threads.append(t)
    try:
        yield servers
    finally:
        for s in servers:
            s.stop()
        for t in threads:
            t.join(timeout=5.0)


def _serve(server):
    threading.Thread(target=server.serve_forever, daemon=True).start()


def _stop_and_wait(server, timeout=5.0):
    """Stop a served shard and wait for its listener to actually close
    (so a relaunch can rebind the same port)."""
    server.stop()
    deadline = time.monotonic() + timeout
    while server._listen is not None and time.monotonic() < deadline:
        time.sleep(0.02)
    assert server._listen is None, "server did not release its port"


def _addrs(servers):
    return [(HOST, s.port) for s in servers]


def _train_rounds(remote, oracle, *, rounds, vocab, dim, seed=0):
    rng = np.random.default_rng(seed)
    for _ in range(rounds):
        ids = rng.choice(vocab, size=min(10, vocab), replace=False)
        ids = ids.astype(np.int64)
        g = rng.standard_normal((len(ids), dim)).astype(np.float32)
        np.testing.assert_array_equal(remote.pull(ids), oracle.pull(ids))
        remote.push(ids, g)
        oracle.push(ids, g)
    return rng


def _assert_export_identical(got, want):
    assert set(got) == set(want)
    for k in want:
        assert got[k].tobytes() == want[k].tobytes(), k


# -- bit-identity ------------------------------------------------------------

@pytest.mark.parametrize("optimizer", ["sgd", "adagrad"])
def test_remote_matches_in_process_bit_identical(fleet2, optimizer):
    kw = dict(vocab_size=64, dim=4, optimizer=optimizer,
              learning_rate=0.1, seed=7)
    oracle = SparseTable("t", num_shards=2, **kw)
    with RemoteSparseTable("t", addrs=_addrs(fleet2), io_timeout_s=IO_TO,
                           **kw) as rt:
        _train_rounds(rt, oracle, rounds=5, vocab=64, dim=4)
        allids = np.arange(64, dtype=np.int64)
        assert rt.pull(allids).tobytes() == oracle.pull(allids).tobytes()
        if optimizer == "adagrad":
            assert rt.pull_slot("moment", allids).tobytes() \
                == oracle.pull_slot("moment", allids).tobytes()
        assert rt.live_rows == oracle.live_rows
        _assert_export_identical(rt.export_state_vars(),
                                 oracle.export_state_vars())


def test_naive_json_arm_same_rows(fleet2):
    kw = dict(vocab_size=32, dim=4, optimizer="adagrad",
              learning_rate=0.2, seed=3)
    oracle = SparseTable("t", num_shards=2, **kw)
    with RemoteSparseTable("t", addrs=_addrs(fleet2), wire_mode="naive",
                           io_timeout_s=IO_TO, **kw) as rt:
        _train_rounds(rt, oracle, rounds=3, vocab=32, dim=4, seed=9)
        allids = np.arange(32, dtype=np.int64)
        assert rt.pull(allids).tobytes() == oracle.pull(allids).tobytes()


def test_pad_ids_skipped_remote(fleet2):
    kw = dict(vocab_size=16, dim=2, seed=1)
    oracle = SparseTable("t", num_shards=2, **kw)
    with RemoteSparseTable("t", addrs=_addrs(fleet2), io_timeout_s=IO_TO,
                           **kw) as rt:
        ids = np.array([3, -1, 7, -1], np.int64)     # PAD_ID = -1
        np.testing.assert_array_equal(rt.pull(ids), oracle.pull(ids))
        assert np.all(rt.pull(ids)[1] == 0) and np.all(rt.pull(ids)[3] == 0)
        g = np.ones((4, 2), np.float32)
        rt.push(ids, g)
        oracle.push(ids, g)
        allids = np.arange(16, dtype=np.int64)
        assert rt.pull(allids).tobytes() == oracle.pull(allids).tobytes()


# -- checkpoint / restore ----------------------------------------------------

def test_remote_export_restores_into_local_table_any_shards(fleet2):
    kw = dict(vocab_size=48, dim=4, optimizer="adagrad", seed=5)
    oracle = SparseTable("t", num_shards=2, **kw)
    with RemoteSparseTable("t", addrs=_addrs(fleet2), io_timeout_s=IO_TO,
                           **kw) as rt:
        _train_rounds(rt, oracle, rounds=4, vocab=48, dim=4, seed=2)
        state = rt.export_state_vars()
        allids = np.arange(48, dtype=np.int64)
        # remote fleet -> local table under a DIFFERENT shard count
        for n in (1, 3):
            t2 = SparseTable("t", num_shards=n, **kw)
            t2.restore_state_vars(state)
            assert t2.pull(allids).tobytes() == oracle.pull(allids).tobytes()
            assert t2.pull_slot("moment", allids).tobytes() \
                == oracle.pull_slot("moment", allids).tobytes()
        # local 1-shard save -> remote 2-shard fleet
        save = SparseTable("t", num_shards=1, **kw)
        save.restore_state_vars(state)
        rt.restore_state_vars(save.export_state_vars())
        assert rt.pull(allids).tobytes() == oracle.pull(allids).tobytes()


def test_server_checkpoint_and_cold_restart(tmp_path, fleet2):
    kw = dict(vocab_size=32, dim=4, optimizer="adagrad", seed=11)
    oracle = SparseTable("t", num_shards=1, **kw)
    s = PServer(0, 1, host=HOST, dir=str(tmp_path), io_timeout_s=IO_TO)
    port = s.start()
    _serve(s)
    with RemoteSparseTable("t", addrs=[(HOST, port)], io_timeout_s=IO_TO,
                           **kw) as rt:
        _train_rounds(rt, oracle, rounds=3, vocab=32, dim=4, seed=4)
        rt.checkpoint()
    applied = s.pushes_applied
    _stop_and_wait(s)
    # cold restart from the checkpoint dir: rows, slots, dedup state and
    # the pushes_applied chaos counter all come back
    s2 = PServer(0, 1, host=HOST, port=port, dir=str(tmp_path),
                 io_timeout_s=IO_TO)
    s2.start()
    assert s2.pushes_applied == applied
    _serve(s2)
    with RemoteSparseTable("t", addrs=[(HOST, port)], io_timeout_s=IO_TO,
                           **kw) as rt2:
        allids = np.arange(32, dtype=np.int64)
        assert rt2.pull(allids).tobytes() == oracle.pull(allids).tobytes()
        assert rt2.pull_slot("moment", allids).tobytes() \
            == oracle.pull_slot("moment", allids).tobytes()
    s2.stop()


# -- exactly-once pushes -----------------------------------------------------

def test_push_retry_dedup_exactly_once():
    s = PServer(0, 1, host=HOST)          # direct op-level unit test
    s._op_create({"spec": {"name": "t", "vocab_size": 8, "dim": 2,
                           "learning_rate": 1.0,
                           "init": ["constant", 0.0]}}, ())
    ids = np.array([1, 3], np.int64)
    g = np.ones((2, 2), np.float32)
    hdr = {"op": "push", "table": "t", "cid": "c1", "seq": 0, "lr": None}
    r1, _ = s._op_push(dict(hdr), (ids, g))
    assert r1["updated"] == 2 and "dup" not in r1
    # the client's retry replays the SAME (cid, seq): ack, don't apply
    r2, _ = s._op_push(dict(hdr), (ids, g))
    assert r2.get("dup") is True and r2["updated"] == 0
    assert s.pushes_applied == 1
    rows, _arrs = s._op_pull({"op": "pull", "table": "t"}, (ids,))
    (pulled,) = _arrs
    np.testing.assert_array_equal(pulled, -np.ones((2, 2), np.float32))
    # a NEW seq from the same client applies again
    r3, _ = s._op_push({**hdr, "seq": 1}, (ids, g))
    assert r3["updated"] == 2 and s.pushes_applied == 2


def test_cid_globally_unique_shape():
    # shards dedup pushes on (cid, seq): a pid-only cid collides across
    # hosts (containers reuse low pids) and silently dup-acks the second
    # client's pushes, so the cid carries hostname + pid + a random
    # component and never repeats within a process either
    import os
    import socket
    kw = dict(vocab_size=8, dim=2, addrs=[(HOST, 1)])
    cids = {RemoteSparseTable("t", **kw)._cid for _ in range(8)}
    assert len(cids) == 8
    for cid in cids:
        assert cid.startswith(f"{socket.gethostname()}.{os.getpid()}.")


def test_faultinject_rpc_transient_is_retried(fleet2):
    kw = dict(vocab_size=32, dim=4, seed=0)
    oracle = SparseTable("t", num_shards=2, **kw)
    with RemoteSparseTable(
            "t", addrs=_addrs(fleet2), io_timeout_s=IO_TO,
            retry=RetryPolicy(max_attempts=4, backoff_base_s=0.01,
                              jitter=0.0), **kw) as rt:
        # both shards dialled before the fault is armed: the site counts
        # every frame, a connection's ``hello`` too, and a transient error
        # in answer to a hello is a wiring mismatch, not a retry (which
        # frame came third was the servers' threads' to decide)
        allids = np.arange(32, dtype=np.int64)
        assert rt.pull(allids).tobytes() == oracle.pull(allids).tobytes()
        faultinject.configure("pserver.rpc@3=transient")
        try:
            _train_rounds(rt, oracle, rounds=3, vocab=32, dim=4, seed=6)
        finally:
            faultinject.clear()
        assert rt.pull(allids).tobytes() == oracle.pull(allids).tobytes()


def test_faultinject_rpc_drop_reconnects_and_dedups(fleet2):
    kw = dict(vocab_size=32, dim=4, seed=0)
    oracle = SparseTable("t", num_shards=2, **kw)
    with RemoteSparseTable(
            "t", addrs=_addrs(fleet2), io_timeout_s=IO_TO,
            retry=RetryPolicy(max_attempts=5, backoff_base_s=0.01,
                              jitter=0.0), **kw) as rt:
        # drop the connection on two mid-train frames: the client sees a
        # torn frame, reconnects, replays; (cid, seq) dedup keeps the
        # replayed pushes exactly-once
        faultinject.configure("pserver.rpc@6=drop;pserver.rpc@9=drop")
        try:
            _train_rounds(rt, oracle, rounds=4, vocab=32, dim=4, seed=8)
        finally:
            faultinject.clear()
        allids = np.arange(32, dtype=np.int64)
        assert rt.pull(allids).tobytes() == oracle.pull(allids).tobytes()


def test_rpc_drop_without_retry_budget_surfaces(fleet2):
    kw = dict(vocab_size=8, dim=2, seed=0)
    with RemoteSparseTable(
            "t", addrs=_addrs(fleet2), io_timeout_s=IO_TO,
            retry=RetryPolicy(max_attempts=1), **kw) as rt:
        rt.pull(np.array([1], np.int64))    # connect + create first
        faultinject.configure("pserver.rpc@*=drop")
        try:
            with pytest.raises(RetriesExhausted):
                rt.pull(np.array([2], np.int64))
        finally:
            faultinject.clear()


# -- typed refusals ----------------------------------------------------------

def test_spec_mismatch_refused_fatal(fleet2):
    kw = dict(vocab_size=32, dim=4, seed=0)
    with RemoteSparseTable("t", addrs=_addrs(fleet2), io_timeout_s=IO_TO,
                           **kw) as rt:
        rt.pull(np.array([1], np.int64))
    with RemoteSparseTable("t", addrs=_addrs(fleet2), io_timeout_s=IO_TO,
                           vocab_size=32, dim=8, seed=0) as bad:
        with pytest.raises(RemoteTableError, match="different spec"):
            bad.pull(np.array([1], np.int64))


def test_fleet_wiring_mismatch_refused(fleet2):
    kw = dict(vocab_size=16, dim=2, seed=0)
    # a 2-shard fleet dialed as if it were ONE shard: shard 0 answers
    # hello with n_shards=2 and the client refuses to scatter rows into
    # a fleet it would misroute
    with RemoteSparseTable("t", addrs=[_addrs(fleet2)[0]],
                           io_timeout_s=IO_TO, **kw) as rt:
        with pytest.raises(RemoteTableError, match="wiring"):
            rt.pull(np.array([1], np.int64))
    # shard order swapped: hello says shard 1 where the client dialed 0
    with RemoteSparseTable("t", addrs=list(reversed(_addrs(fleet2))),
                           io_timeout_s=IO_TO, **kw) as rt:
        with pytest.raises(RemoteTableError, match="wiring"):
            rt.pull(np.array([1], np.int64))


# -- chain-backup replication ------------------------------------------------

def test_chain_backup_survives_shard_death(fleet2):
    kw = dict(vocab_size=64, dim=4, optimizer="adagrad",
              learning_rate=0.1, seed=7)
    oracle = SparseTable("t", num_shards=2, **kw)
    s0, s1 = fleet2
    with RemoteSparseTable("t", addrs=_addrs(fleet2), io_timeout_s=IO_TO,
                           **kw) as rt:
        rng = _train_rounds(rt, oracle, rounds=6, vocab=64, dim=4, seed=1)
        applied0 = s0.pushes_applied
        assert applied0 > 0 and s1.pushes_applied > 0
        # shard 1 holds a backup copy for shard 0 (and vice versa)
        assert any(origin == 0 for origin, _ in s1._backups)
        assert any(origin == 1 for origin, _ in s0._backups)

        # kill shard 0 (no checkpoint dir: the BACKUP is the only copy),
        # relaunch on the same port, recover from shard 1
        _stop_and_wait(s0)
        s0b = PServer(0, 2, host=HOST, port=s0.port,
                      backup_addr=(HOST, s1.port), io_timeout_s=IO_TO)
        s0b.start()
        assert s0b.pushes_applied == applied0   # counter restored too
        _serve(s0b)

        # the SAME client keeps training through the relaunch (its
        # reconnect rim re-dials shard 0 transparently)
        for _ in range(3):
            ids = rng.choice(64, size=10, replace=False).astype(np.int64)
            g = rng.standard_normal((10, 4)).astype(np.float32)
            rt.push(ids, g)
            oracle.push(ids, g)
        allids = np.arange(64, dtype=np.int64)
        assert rt.pull(allids).tobytes() == oracle.pull(allids).tobytes()
        assert rt.pull_slot("moment", allids).tobytes() \
            == oracle.pull_slot("moment", allids).tobytes()
        _assert_export_identical(rt.export_state_vars(),
                                 oracle.export_state_vars())
        s0b.stop()


# -- SparseSession composition -----------------------------------------------

def _sparse_program(vocab=32, dim=4, name="tbl"):
    ids = layers.data("ids", shape=[1], dtype="int64")
    label = layers.data("label", shape=[1], dtype="float32")
    emb = layers.embedding(ids, size=[vocab, dim], sparse=True, name=name)
    fc = layers.fc(emb, size=1)
    loss = layers.mean(layers.square(fc - label))
    pt.optimizer.SGD(learning_rate=0.1).minimize(loss)
    return loss


def test_session_binds_remote_table_bit_identical(fleet2):
    _sparse_program(vocab=32, dim=4)
    kw = dict(vocab_size=32, dim=4, learning_rate=1.0, seed=13)
    local = SparseTable("tbl", num_shards=2, **kw)
    with RemoteSparseTable("tbl", addrs=_addrs(fleet2), io_timeout_s=IO_TO,
                           **kw) as rt:
        remote_sess = SparseSession(rt)          # duck-typed single table
        local_sess = SparseSession(local)
        for sess in (remote_sess, local_sess):
            sess.bind(pt.default_main_program())
        ids = np.array([[5], [9], [5], [30]], np.int64)
        feed = {"ids": ids, "label": np.zeros((4, 1), np.float32)}
        fr = remote_sess.prepare_feed(dict(feed))
        fl = local_sess.prepare_feed(dict(feed))
        assert fr["tbl@ROWS"].tobytes() == fl["tbl@ROWS"].tobytes()
        np.testing.assert_array_equal(fr["tbl@RIDX"], fl["tbl@RIDX"])
        g = np.ones_like(fr["tbl@ROWS"])
        remote_sess.complete([g])
        local_sess.complete([g])
        allids = np.arange(32, dtype=np.int64)
        assert rt.pull(allids).tobytes() == local.pull(allids).tobytes()


# -- fleet stats -------------------------------------------------------------

def test_fleet_stats_piggyback(fleet2):
    kw = dict(vocab_size=32, dim=4, seed=0)
    with RemoteSparseTable("t", addrs=_addrs(fleet2), io_timeout_s=IO_TO,
                           **kw) as rt:
        ids = np.arange(10, dtype=np.int64)
        rt.pull(ids)
        assert rt.live_rows == 10               # absorbed from replies
        stats = rt.fleet_stats()
        assert set(stats) == {0, 1}
        assert sum(s["tables"]["t"]["live_rows"]
                   for s in stats.values()) == 10
        assert all(s["pushes_applied"] == 0 for s in stats.values())

"""High-level trainer with the v2 event-loop surface.

Reference: python/paddle/v2/trainer.py (SGD :124 train loop, event_handler
protocol python/paddle/v2/event.py) — the API the reference's demos and
benchmarks drive (v1_api_demo/mnist/api_train.py).  Internally this builds
the fluid-style program (optimizer.minimize + Executor) — the two reference
generations collapse into one path here.
"""
from __future__ import annotations

import os
import signal as _signal
from typing import Callable, List, Optional, Sequence

import numpy as np

from . import observability
from . import optimizer as optimizer_mod
from .core.executor import Executor
from .core.program import (Program, Variable, default_main_program,
                           default_startup_program)
from .core.scope import global_scope
from .data_feeder import DataFeeder
from .testing import faultinject as _fi


class events:
    """Event types passed to event_handler (python/paddle/v2/event.py)."""

    class BeginPass:
        def __init__(self, pass_id):
            self.pass_id = pass_id

    class EndPass:
        def __init__(self, pass_id, evaluator=None):
            self.pass_id = pass_id
            self.evaluator = evaluator

    class BeginIteration:
        def __init__(self, pass_id, batch_id):
            self.pass_id = pass_id
            self.batch_id = batch_id

    class EndIteration:
        def __init__(self, pass_id, batch_id, cost, metrics):
            self.pass_id = pass_id
            self.batch_id = batch_id
            self.cost = cost
            self.metrics = metrics


class SGD:
    """v2-style trainer: SGD(cost, parameters=None, update_equation=opt).

    ``update_equation`` is any paddle_tpu.optimizer.Optimizer (the v2 API
    took a v2 optimizer; same role).  ``extra_layers`` are fetched alongside
    cost every iteration and reported in EndIteration.metrics.
    """

    def __init__(self, cost: Variable, parameters=None,
                 update_equation=None, extra_layers: Sequence = (),
                 is_local=True, place=None):
        self.cost = cost
        self.extra = list(extra_layers or ())
        self.optimizer = update_equation or optimizer_mod.SGD(
            learning_rate=0.01)
        self.main_program = cost.block.program
        self.optimizer.minimize(cost)
        self.exe = Executor(place)
        self._initialized = False

    # -- training ----------------------------------------------------------
    def train(self, reader: Callable, num_passes: int = 1,
              event_handler: Optional[Callable] = None,
              feeding=None, feed_list: Optional[Sequence[Variable]] = None,
              steps_per_dispatch: int = 1, pipeline=False,
              warmup: bool = False, validate: Optional[bool] = None,
              autotune: Optional[bool] = None,
              auto_shard=None,
              checkpoint_dir: Optional[str] = None, resume: bool = False,
              save_every_n_steps: Optional[int] = None, master=None,
              handle_signals: bool = True, elastic=None,
              sparse_tables=None):
        """reader yields batches (lists of rows); feeding maps data-layer
        names to row positions (v2 trainer.py feeding) or pass feed_list.

        ``steps_per_dispatch > 1`` stacks runs of consecutive same-shape
        batches and executes each run as ONE device-side scan
        (`Executor.run_steps` with stacked feeds) — the compiled training
        loop.  Iteration events still fire per batch (after the dispatch
        that contained them); differently-shaped batches (bucketed
        padding) fall back to per-batch dispatch automatically.

        ``pipeline`` turns on the asynchronous input pipeline
        (``Executor.run_pipelined``): batch decode, padding and
        ``device_put`` staging move onto worker threads overlapped with
        device compute, and same-shape runs dispatch as compiled K-step
        scans.  Pass ``True`` for defaults or a dict with any of
        ``steps_per_dispatch`` (default 8, or the ``steps_per_dispatch``
        argument when > 1), ``num_workers`` (reader prefetch workers,
        default 1; 0 folds decode into the staging thread, right when
        host cores are scarce; more than 1 reorders batches), ``buffer_size``
        (decoded-batch queue bound, default 4) and ``prefetch_depth``
        (staged dispatches in flight, default 2).  Step math is identical
        to the per-batch loop; only event timing changes (events for a
        dispatch fire after it completes).

        ``warmup=True`` pays trace/lower/compile BEFORE the training loop
        starts: one batch is peeked from ``reader`` (for its shapes only)
        and the step variant(s) this loop will dispatch are compiled ahead
        of time (``Executor.compile``), so the first real batch executes a
        ready executable.  The XLA compiles land in JAX's persistent
        compilation cache (``core.compile_cache.cache_dir()``), so a
        deploy-step warmup also serves later processes.  Bucketed readers
        whose later batches change shape still compile those variants on
        first use.

        ``validate=True`` runs the static program verifier
        (``paddle_tpu.analysis``) over the startup and training programs
        before their first trace: a malformed graph fails with a stable
        ``PT0xx`` diagnostic naming the op instead of a JAX trace error.
        ``False`` forces it off; ``None`` (default) defers to the
        ``validate`` flag (``PADDLE_TPU_VALIDATE=1``).  The override
        applies to this call only — the executor's own setting is
        restored afterwards.

        ``autotune=True`` replays persisted autotuner winners
        (``paddle_tpu.tuning``) into this loop's omitted knobs: the
        pipelined path's ``steps_per_dispatch``/``prefetch_depth`` and
        reader ``num_workers``/``buffer_size`` (any knob given
        explicitly — argument or pipeline dict — always wins).
        ``False`` forces it off; ``None`` (default) defers to the
        executor / ``autotune`` flag (``PADDLE_TPU_AUTOTUNE=1``).
        Replay never searches — with no persisted record every knob
        keeps its hand-picked default.  Search with ``python -m paddle_tpu tune
        <target>``.  Like ``validate``, the override applies to this
        call only.

        ``auto_shard`` turns on the static auto-sharding planner
        (``paddle_tpu.analysis.planner``): when the executor's
        ``param_specs``/``feed_specs`` are omitted, a plan proposed for
        its mesh (validated by the PT030/PT031 lints) fills them before
        the first trace.  ``True`` requires the trainer's executor to
        already be a ``ShardedExecutor``; a ``{'dp': 8}`` dict or a
        ``"dp=8,tp=2"`` string additionally builds the mesh over the
        local devices and swaps the trainer onto a
        ``ShardedExecutor(auto_shard=True)`` (only before the first
        ``train()`` call — the swap must precede parameter init).

        ``checkpoint_dir`` turns on the fault-tolerant runtime
        (``paddle_tpu.train_state``): every ``save_every_n_steps``
        completed batches a checkpoint of the full scope PLUS the loop's
        :class:`~paddle_tpu.train_state.TrainState` (step/pass/batch
        counters — the RNG derivation state) commits atomically, and a
        SIGTERM/SIGINT finishes the in-flight dispatch, commits an
        emergency checkpoint and exits
        :data:`~paddle_tpu.faults.EXIT_PREEMPTED` (raise:
        :class:`~paddle_tpu.faults.Preempted`) so a supervisor
        (``distributed.supervisor``) relaunches it.  ``resume=True``
        restores the newest intact checkpoint and continues — with a
        deterministic, restartable ``reader`` and an order-preserving
        pipeline config (``num_workers <= 1``) the resumed run's fetches
        are BIT-IDENTICAL to an uninterrupted one (the chaos suite pins
        this with subprocess kills); an empty directory starts fresh, so
        a supervised command can always pass ``resume=True``.  Saves
        happen only at dispatch boundaries (scope consistency); with
        chunked dispatch the effective cadence rounds up to the chunk.
        ``master``: an in-process ``distributed.Master`` whose task-queue
        snapshot should commit alongside each checkpoint (and be restored
        on resume).  ``handle_signals=False`` skips installing handlers
        (e.g. when embedding the trainer in a host that owns them).

        ``elastic``: a duck-typed elastic-worker hook (normally a
        ``distributed.elastic.ElasticWorker`` — the trainer itself never
        imports the elastic module, so the zero-cost-when-unused
        contract holds statically).  The hook's ``state()`` rides in
        every checkpoint's ``TrainState.elastic``; ``bind(ckpt, ts)``
        runs after restore (registering with the membership layer and
        rewinding the master-sharded stream — which is WHY the
        batch-skip resume fast-forward is forced to zero here: a
        master-backed stream resumes by task re-serve + within-task
        offset, not by replaying the reader from the top);
        ``after_batch()`` runs per completed batch (heartbeat, drain
        command, injection sites, post-commit ``task_finished``);
        ``on_complete()`` runs after the final save.  Requires
        ``checkpoint_dir`` and the per-batch dispatch path
        (``steps_per_dispatch == 1``, no ``pipeline``) — the elastic
        commit protocol needs every batch to be a dispatch boundary.

        ``sparse_tables``: a duck-typed host sparse-table session
        (normally a :class:`paddle_tpu.sparse.SparseSession` — the
        trainer itself never imports the sparse package, so the
        zero-cost-when-unused contract holds statically).  Per batch the
        loop calls ``prepare_feed`` (id dedup → host pull → rows/inverse
        feed injection) before the dispatch and ``complete`` with the
        fetched ``<rows>@GRAD`` arrays after it (the host-side sparse
        optimizer push).  The per-batch path is fully synchronous by
        default — pull → step → push, the semantics the dense-parity
        test pins bit-identical; the chunked (``steps_per_dispatch >
        1``) and ``pipeline`` paths pull up to a dispatch-chunk (plus
        prefetch depth) ahead of the pushes — bounded-staleness ASYNC
        updates, the reference's async-pserver SGD semantics.  A
        session with ``prefetch_depth > 0`` additionally overlaps: all
        three paths route raw feeds through ``prefetch_feeds`` so batch
        N+1's host pulls run on the session's worker while batch N
        dispatches (``BeginIteration`` then fires after its batch's
        feed was prepared — preparation is ahead of the loop by
        design), and a session with ``async_push > 0`` applies pushes
        on a session worker with ``flush()`` barriers at every
        checkpoint export, every ``test()`` pull, and train() end.
        With ``checkpoint_dir`` the session's tables ride inside every
        checkpoint (``Checkpointer(state_vars=...)``) and restore on
        ``resume``.  Not combinable with ``elastic`` or ``warmup``.
        """
        event_handler = event_handler or (lambda e: None)
        if not checkpoint_dir:
            # fail loudly, not silently unprotected: every one of these
            # asks for checkpointing machinery that needs a directory
            if resume:
                raise ValueError("train(resume=True) requires "
                                 "checkpoint_dir")
            if save_every_n_steps is not None:
                raise ValueError("train(save_every_n_steps=...) requires "
                                 "checkpoint_dir")
            if master is not None:
                raise ValueError("train(master=...) snapshots the task "
                                 "queue into checkpoints — pass "
                                 "checkpoint_dir")
            if elastic is not None:
                raise ValueError("train(elastic=...) commits its stream "
                                 "position inside checkpoints — pass "
                                 "checkpoint_dir")
        if elastic is not None and (pipeline or steps_per_dispatch > 1):
            raise ValueError(
                "train(elastic=...) needs the per-batch dispatch path "
                "(steps_per_dispatch=1, pipeline=False): the elastic "
                "task-commit protocol saves at every batch boundary")
        sess = sparse_tables
        if sess is not None:
            if elastic is not None:
                raise NotImplementedError(
                    "train(sparse_tables=..., elastic=...): the elastic "
                    "resize merge has no in-process sparse-row story — "
                    "host the rows outside the worker fleet instead: "
                    "bind a RemoteSparseTable against a pserver fleet "
                    "(python -m paddle_tpu pserver) so workers come and "
                    "go while the row store stays put")
            if warmup:
                raise ValueError(
                    "train(sparse_tables=..., warmup=True) is not "
                    "supported: warmup compiles from a raw peeked batch "
                    "without the session's injected rows feeds")
            sess.bind(self.main_program)
        if auto_shard:
            self._enable_auto_shard(auto_shard)
        # validate is a PER-CALL override: restore the executor's own
        # setting afterwards so a later train() with the default None
        # defers to the flag again
        prev_validate = self.exe.validate
        if validate is not None:
            self.exe.validate = validate
        # autotune is the same kind of per-call override (the executor's
        # own dispatch paths consult _autotuning() for their tuned knobs)
        prev_autotune = self.exe.autotune
        if autotune is not None:
            self.exe.autotune = autotune
        ckpt = None
        try:
            if not self._initialized:
                self.exe.run(default_startup_program(), feed={}, fetch_list=[])
                self._initialized = True

            start_pass, resume_skip = 0, 0
            if checkpoint_dir:
                from .train_state import Checkpointer
                opt_fp = {"type": type(self.optimizer).__name__}
                lr = getattr(self.optimizer, "_learning_rate", None)
                if isinstance(lr, (int, float)):
                    opt_fp["learning_rate"] = float(lr)
                ckpt = Checkpointer(checkpoint_dir, self.exe,
                                    save_every_n_steps=save_every_n_steps,
                                    master=master,
                                    handle_signals=handle_signals,
                                    extra_state=(elastic.state
                                                 if elastic is not None
                                                 else None),
                                    state_vars=(sess.export_state_vars
                                                if sess is not None
                                                else None),
                                    delta_source=sess)
                ts = None
                if resume:
                    ts = ckpt.restore(
                        global_scope(),
                        expect_seed=self.main_program.random_seed,
                        expect_optimizer=opt_fp)
                if ts is not None and sess is not None:
                    # table rows/slots rode the checkpoint as synthetic
                    # __sparse__/ scope vars; pop them into the session's
                    # tables so the host state resumes atomically with
                    # the model
                    if not sess.restore_from_scope(global_scope()):
                        raise ValueError(
                            "train(resume=True, sparse_tables=...): the "
                            "restored checkpoint carries no sparse-table "
                            "state — it was written by a run without "
                            "sparse_tables")
                if ts is not None:
                    # the step counter IS the per-step RNG derivation
                    # state: restoring it restores every random op's
                    # key stream exactly
                    self.exe._step = ts.exe_step
                    start_pass, resume_skip = ts.pass_id, ts.batch_id
                    if master is not None and ts.master is not None \
                            and hasattr(master, "load_state_dict"):
                        # queue position from INSIDE the checkpoint —
                        # atomically consistent with the model restored
                        master.load_state_dict(ts.master)
                ckpt.begin(global_scope(), ts,
                           self.main_program.random_seed, opt_fp)
                if elastic is not None:
                    # register with the membership layer and rewind the
                    # master-sharded stream to the COMMITTED position;
                    # the stream resumes by task re-serve + within-task
                    # offset, so the batch-skip fast-forward must not
                    # also skip (it would double-skip the replay).  The
                    # pass cursor is also stream-defined: a drained
                    # worker's final state says pass_id=num_passes, but
                    # its shard may still hold work (or regain some
                    # after a resize) — always re-enter the pass loop
                    # and let the master decide whether anything is
                    # left (an already-complete worker pulls nothing
                    # and final_save's idempotency skips the re-commit)
                    elastic.bind(ckpt, ts)
                    start_pass, resume_skip = 0, 0

            fetch = [self.cost] + self.extra
            n_fetch = len(fetch)
            # sparse sessions fetch each table's dense <rows>@GRAD
            # alongside the model fetches; `finish` pushes them back to
            # the host tables and strips them before events fire
            sfetch = fetch + (sess.grad_fetch_list if sess is not None
                              else [])

            def finish(out):
                if sess is None:
                    return out
                sess.complete(out[n_fetch:])
                return out[:n_fetch]

            # resolve the pipelined-loop knobs ONCE — including the
            # autotuned fills — so warmup AOT-compiles the exact scan
            # variant the loop will dispatch (_dispatch_k's contract;
            # resolving inside the loop body would let warmup compile
            # the untuned K and the first real dispatch pay the stall)
            pipe_opts = None
            if pipeline:
                pipe_opts = dict(pipeline) if isinstance(pipeline, dict) \
                    else {}
                if self.exe._autotuning():
                    self._fill_tuned_pipeline_opts(pipe_opts,
                                                   steps_per_dispatch)
            if warmup:
                self._warmup(reader, feeding, feed_list, fetch,
                             steps_per_dispatch,
                             pipe_opts if pipe_opts is not None else False)

            # periodic observability reports every `log_period` iterations
            # (the v1 Stat::printAllStatus cadence, Flags.cpp:62), counted
            # across passes (and across restarts when resuming); no-op
            # unless observing
            iters_done = ckpt.iters_done if ckpt is not None else 0
            observing = self.exe._observing()
            # global batch cursor (across passes AND restarts): the index
            # key of the trainer.step/reader.item injection sites, so a
            # resumed run never re-fires a spec entry it already passed
            gcount = [ckpt.emitted if ckpt is not None else 0]

            def emit_end(pass_id, batch_id, out):
                nonlocal iters_done
                # step snapshot BEFORE the handler runs: a handler that
                # does extra executor work (trainer.test) must not blur
                # this batch's dispatch-boundary detection
                step_now = self.exe._step
                metrics = {getattr(v, "name", str(i)): out[1 + i]
                           for i, v in enumerate(self.extra)}
                event_handler(events.EndIteration(
                    pass_id, batch_id, float(out[0]), metrics))
                iters_done += 1
                observability.maybe_periodic_report(iters_done,
                                                    observing=observing)
                gcount[0] += 1
                if _fi.ENABLED:
                    action = _fi.check("trainer.step", index=gcount[0])
                    if action == "preempt":
                        if ckpt is None:
                            # fail loudly: the spec asked for a graceful
                            # preemption this run cannot perform
                            raise _fi.InjectedFault(
                                "trainer.step=preempt injected but "
                                "train() has no checkpoint_dir")
                        ckpt.request_preempt()
                    elif action == "sigterm":
                        os.kill(os.getpid(), _signal.SIGTERM)
                    elif action == "kill":
                        # REAL SIGKILL: dies with returncode -9, which a
                        # supervisor treats as relaunchable signal death
                        os.kill(os.getpid(), _signal.SIGKILL)
                    elif action is not None:
                        _fi.raise_for(action, "trainer.step", gcount[0])
                if ckpt is not None:
                    ckpt.on_batch_done(pass_id, batch_id, step_now)
                if elastic is not None:
                    elastic.after_batch()

            # reader wrapper: resume skip for the first resumed pass +
            # the reader.item injection site.  The plain path stays the
            # raw reader — zero new per-step work when fault tolerance
            # and injection are off.
            rcount = [gcount[0]]

            def pass_reader(pass_id):
                skip = resume_skip if pass_id == start_pass else 0
                if skip == 0 and not _fi.ENABLED:
                    return reader, 0

                def _r():
                    for i, b in enumerate(reader()):
                        if i < skip:
                            continue
                        rcount[0] += 1
                        if _fi.ENABLED:
                            a = _fi.check("reader.item", index=rcount[0])
                            if a is not None:
                                _fi.raise_for(a, "reader.item", rcount[0])
                        yield b
                return _r, skip

            if pipeline:
                opts = pipe_opts
                K = self._dispatch_k(opts, steps_per_dispatch)
                workers = int(opts.get("num_workers", 1))
                buf = int(opts.get("buffer_size", 4))
                depth = int(opts.get("prefetch_depth", 2))
                # feed() results live at most until their chunk is stacked /
                # shipped — K pending plus in-flight slack bounds liveness
                feeder = self._feeder(feeding, feed_list, staging_slots=K + 2)
                from .reader.pipeline import prefetch
                for pass_id in range(start_pass, num_passes):
                    event_handler(events.BeginPass(pass_id))
                    if ckpt is not None:
                        ckpt.resync()
                    # num_workers=0: no reader prefetch stage — decode runs in
                    # run_pipelined's staging thread (one host thread total;
                    # right when host cores are scarce)
                    r, skip = pass_reader(pass_id)
                    src = prefetch(r, buffer_size=buf,
                                   num_workers=workers) if workers > 0 \
                        else r
                    feed_iter = (feeder.feed(b) for b in src())
                    if sess is not None:
                        # pulls run ahead of the pushes (the staging
                        # thread — plus the session's own pull-ahead
                        # worker when prefetch_depth > 0): bounded-
                        # staleness async updates (see docstring)
                        if getattr(sess, "prefetch_depth", 0) > 0:
                            feed_iter = sess.prefetch_feeds(feed_iter)
                        else:
                            feed_iter = (sess.prepare_feed(f)
                                         for f in feed_iter)
                    gen = self.exe.run_pipelined(
                        feed_iter, self.main_program, fetch_list=sfetch,
                        steps_per_dispatch=K, prefetch_depth=depth)
                    try:
                        for batch_id, out in enumerate(gen, start=skip):
                            out = finish(out)
                            event_handler(events.BeginIteration(pass_id,
                                                                batch_id))
                            emit_end(pass_id, batch_id, out)
                    finally:
                        # a mid-pass failure must deterministically stop
                        # the whole feed chain, not wait for GC: close
                        # the pipelined generator FIRST (its contract
                        # stops and joins the staging worker that may be
                        # executing feed_iter right now — closing
                        # feed_iter before that join would race a
                        # running generator), then the feed source (the
                        # session's pull-ahead worker, when prefetching)
                        gen.close()
                        feed_iter.close()
                    event_handler(events.EndPass(pass_id))
                if sess is not None and hasattr(sess, "flush"):
                    sess.flush()     # async-push barrier at train end
                if ckpt is not None:
                    ckpt.final_save(num_passes)
                return

            feeder = self._feeder(feeding, feed_list)

            def flush(pass_id, first_id, chunk):
                if len(chunk) == 1:
                    event_handler(events.BeginIteration(pass_id, first_id))
                    out = finish(self.exe.run(
                        self.main_program, feed=chunk[0],
                        fetch_list=sfetch))
                    emit_end(pass_id, first_id, out)
                    return
                from .core.executor import stack_feeds
                stacked = stack_feeds(chunk)
                outs = self.exe.run_steps(
                    len(chunk), self.main_program, feed=stacked,
                    fetch_list=sfetch, feeds_stacked=True)
                for i in range(len(chunk)):
                    event_handler(events.BeginIteration(pass_id, first_id + i))
                    emit_end(pass_id, first_id + i,
                             finish([o[i] for o in outs]))

            for pass_id in range(start_pass, num_passes):
                event_handler(events.BeginPass(pass_id))
                if ckpt is not None:
                    ckpt.resync()
                r, skip = pass_reader(pass_id)
                sess_prefetch = sess is not None and \
                    getattr(sess, "prefetch_depth", 0) > 0
                if steps_per_dispatch <= 1:
                    if sess_prefetch:
                        # pull-ahead rim: batch N+1's host pulls run on
                        # the session worker while batch N dispatches
                        feeds = sess.prefetch_feeds(
                            feeder.feed(b) for b in r())
                        try:
                            for batch_id, feed in enumerate(feeds,
                                                            start=skip):
                                event_handler(events.BeginIteration(
                                    pass_id, batch_id))
                                out = finish(self.exe.run(
                                    self.main_program, feed=feed,
                                    fetch_list=sfetch))
                                emit_end(pass_id, batch_id, out)
                        finally:
                            feeds.close()
                        event_handler(events.EndPass(pass_id))
                        continue
                    for batch_id, batch in enumerate(r(), start=skip):
                        event_handler(events.BeginIteration(pass_id, batch_id))
                        feed = feeder.feed(batch)
                        if sess is not None:
                            # synchronous rim: pull -> step -> push
                            feed = sess.prepare_feed(feed)
                        out = finish(self.exe.run(self.main_program,
                                                  feed=feed,
                                                  fetch_list=sfetch))
                        emit_end(pass_id, batch_id, out)
                    event_handler(events.EndPass(pass_id))
                    continue
                if sess is None:
                    feed_src = (feeder.feed(b) for b in r())
                elif sess_prefetch:
                    # pull-ahead rim over the chunked path
                    feed_src = sess.prefetch_feeds(
                        feeder.feed(b) for b in r())
                else:
                    # chunk-granular staleness: all K pulls precede
                    # the chunk's dispatch (async-pserver semantics)
                    feed_src = (sess.prepare_feed(feeder.feed(b))
                                for b in r())
                chunk, first_id, sig = [], 0, None
                try:
                    for batch_id, feed in enumerate(feed_src, start=skip):
                        fsig = tuple(sorted(
                            (k, np.shape(v), str(np.asarray(v).dtype))
                            for k, v in feed.items()))
                        if chunk and fsig != sig:
                            flush(pass_id, first_id, chunk)
                            chunk = []
                        if not chunk:
                            first_id, sig = batch_id, fsig
                        chunk.append(feed)
                        if len(chunk) == steps_per_dispatch:
                            flush(pass_id, first_id, chunk)
                            chunk = []
                    if chunk:
                        flush(pass_id, first_id, chunk)
                finally:
                    feed_src.close()
                event_handler(events.EndPass(pass_id))
            if sess is not None and hasattr(sess, "flush"):
                sess.flush()         # async-push barrier at train end
            if ckpt is not None:
                ckpt.final_save(num_passes)
            if elastic is not None:
                # the final save above committed the last task's state;
                # the hook now reports it finished and deregisters
                elastic.on_complete()
        finally:
            self.exe.validate = prev_validate
            self.exe.autotune = prev_autotune
            if ckpt is not None:
                ckpt.close()

    def test(self, reader: Callable, feeding=None, feed_list=None,
             sparse_tables=None):
        """Average cost (+extras) over a reader without updating params.
        ``sparse_tables``: the training session — evaluation pulls rows
        read-only (no grad fetches, no pushes)."""
        feeder = self._feeder(feeding, feed_list)
        test_prog = self.main_program.prune(
            [self.cost] + self.extra).clone(for_test=True)
        if sparse_tables is not None:
            sparse_tables.bind(test_prog)
        totals, count = None, 0
        for batch in reader():
            feed = feeder.feed(batch)
            if sparse_tables is not None:
                feed = sparse_tables.prepare_feed(feed, is_test=True)
            out = self.exe.run(test_prog, feed=feed,
                               fetch_list=[self.cost] + self.extra,
                               is_test=True)
            vals = [np.asarray(o, np.float64) for o in out]
            totals = vals if totals is None else [
                t + v for t, v in zip(totals, vals)]
            count += 1
        if count == 0:
            return None
        return [t / count for t in totals]

    # -- helpers -----------------------------------------------------------
    def _enable_auto_shard(self, auto_shard):
        """Resolve the train(auto_shard=) forms onto the executor."""
        from .parallel.sharded import ShardedExecutor

        if isinstance(self.exe, ShardedExecutor):
            if auto_shard is not True:
                # a mesh form alongside an existing ShardedExecutor must
                # AGREE with its mesh — silently planning for the
                # executor's mesh while the user asked for another would
                # misreport what ran
                if isinstance(auto_shard, str):
                    from .cli import _parse_mesh
                    want = _parse_mesh(auto_shard)
                else:
                    want = {str(k): int(v)
                            for k, v in dict(auto_shard).items()}
                have = {str(a): int(self.exe.mesh.shape[a])
                        for a in self.exe.mesh.axis_names
                        if self.exe.mesh.shape[a] > 1}
                if {k: v for k, v in want.items() if v > 1} != have:
                    raise ValueError(
                        f"train(auto_shard={auto_shard!r}) conflicts "
                        f"with the executor's existing mesh {have} — "
                        f"pass auto_shard=True to plan for that mesh, "
                        f"or build the trainer without a ShardedExecutor")
            self.exe.auto_shard = True
            return
        if auto_shard is True:
            raise ValueError(
                "train(auto_shard=True) needs a ShardedExecutor (its mesh "
                "is the planning target); pass a mesh instead — "
                "auto_shard={'dp': 8} or auto_shard='dp=8,tp=2'")
        if self._initialized:
            raise ValueError(
                "train(auto_shard=<mesh>) must be given on the FIRST "
                "train() call: parameters were already initialized on the "
                "unsharded executor")
        if isinstance(auto_shard, str):
            from .cli import _parse_mesh
            axes = _parse_mesh(auto_shard)
        else:
            axes = {str(k): int(v) for k, v in dict(auto_shard).items()}
        from .parallel.mesh import mesh_for_axes
        self.exe = ShardedExecutor(
            mesh=mesh_for_axes(axes), batch_axis=next(iter(axes), "dp"),
            auto_shard=True)

    def _fill_tuned_pipeline_opts(self, opts, steps_per_dispatch):
        """Fill OMITTED pipeline knobs from persisted autotuner winners
        (autotune opt-in resolved by the caller).  Explicit knobs — in
        the pipeline dict, or steps_per_dispatch > 1 as the documented
        K override — always win; with no persisted record every knob
        resolves to its existing hand-picked default, so this is
        behavior-neutral until a `tune` run has committed a winner."""
        pipe = self.exe._tuned(
            "executor/run_pipelined",
            {"steps_per_dispatch": 8, "prefetch_depth": 2})
        if "steps_per_dispatch" not in opts and steps_per_dispatch <= 1:
            opts["steps_per_dispatch"] = pipe["steps_per_dispatch"]
        if "prefetch_depth" not in opts:
            opts["prefetch_depth"] = pipe["prefetch_depth"]
        rd = self.exe._tuned("reader/prefetch",
                             {"num_workers": 1, "buffer_size": 4})
        if "num_workers" not in opts:
            opts["num_workers"] = rd["num_workers"]
        if "buffer_size" not in opts:
            opts["buffer_size"] = rd["buffer_size"]

    @staticmethod
    def _dispatch_k(opts, steps_per_dispatch):
        """Steps per pipelined dispatch — ONE derivation shared by the
        train loop and _warmup, so warmup always AOT-compiles the exact
        scan variant the loop will dispatch."""
        return int(opts.get("steps_per_dispatch",
                            steps_per_dispatch if steps_per_dispatch > 1
                            else 8))

    def _warmup(self, reader, feeding, feed_list, fetch,
                steps_per_dispatch, pipeline):
        """AOT-compile the step variant(s) the configured loop will use,
        from the shapes of one peeked batch (the batch itself is NOT
        consumed from the training stream — readers are re-callable)."""
        probe = next(iter(reader()), None)
        if probe is None:
            return
        feed0 = self._feeder(feeding, feed_list).feed(probe)
        # train() passes the RESOLVED opts dict (autotuned fills applied)
        # when pipelining — an empty dict still means "pipelined"
        if pipeline is not False and pipeline is not None:
            opts = dict(pipeline) if isinstance(pipeline, dict) else {}
            K = self._dispatch_k(opts, steps_per_dispatch)
        else:
            K = steps_per_dispatch
        # single-step variant: the per-batch path, and the tail/signature-
        # change fallback of the chunked paths
        self.exe.compile(self.main_program, feed=feed0, fetch_list=fetch)
        if K > 1:
            from .core.executor import stack_feeds
            self.exe.compile(self.main_program,
                             feed=stack_feeds([feed0] * K),
                             fetch_list=fetch, num_steps=K,
                             feeds_stacked=True)

    def _feeder(self, feeding, feed_list, staging_slots: int = 0):
        if feed_list is None:
            gb = self.main_program.global_block()
            # session_feed vars (sparse-table rows/inverse) are injected
            # by the SparseSession rim, never by the reader
            data_vars = [v for v in gb.vars.values()
                         if v.is_data and not v.session_feed]
            if feeding is not None:
                order = sorted(feeding, key=lambda k: feeding[k])
                feed_list = [gb.var(n) for n in order]
            else:
                feed_list = data_vars
        return DataFeeder(feed_list, staging_slots=staging_slots)

    def save_parameter_to_tar(self, f=None, dirname=None):
        from . import io
        io.save_params(self.exe, dirname or f, self.main_program)


def infer(output_layer, parameters=None, input=None, feeding=None,
          feed_list=None, executor=None, program: Optional[Program] = None):
    """v2 paddle.infer analog: run the pruned inference slice on a batch."""
    outputs = output_layer if isinstance(output_layer, (list, tuple)) \
        else [output_layer]
    program = program or outputs[0].block.program
    infer_prog = program.prune(outputs).clone(for_test=True)
    exe = executor or Executor()
    gb = program.global_block()
    if feed_list is None:
        if feeding is not None:
            order = sorted(feeding, key=lambda k: feeding[k])
            feed_list = [gb.var(n) for n in order]
        else:
            feed_list = [v for v in gb.vars.values()
                         if v.is_data and not v.session_feed]
    # keep only feeds the pruned program actually reads
    needed = set()
    for op in infer_prog.global_block().ops:
        needed.update(op.input_names)
    feed_list = [v for v in feed_list if v.name in needed]
    feeder = DataFeeder(feed_list)
    feed = feeder.feed(input)
    res = exe.run(infer_prog, feed=feed, fetch_list=outputs, is_test=True)
    return res if len(res) > 1 else res[0]

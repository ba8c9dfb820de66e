"""Multi-tenant inference server: dynamic batching with a robustness
envelope.

Data path, per model (one :class:`~paddle_tpu.serving.model.Model`
tenant each):

    submit ──admission──▶ bounded queue ──batcher──▶ staged batches
                                              │  (stack_feeds + pad to
                                              │   bucket, double-buffered)
                                              ▼
                                        dispatcher ──▶ model fn ──▶ split
                                                                    rows,
                                                                    complete

* **Batching** — the batcher coalesces same-signature requests up to
  ``max_batch``, waiting at most ``max_wait_ms`` after the first one; the
  stacked batch (:func:`~paddle_tpu.core.executor.stack_feeds`) is padded
  up to the next power-of-two **bucket** (:func:`~paddle_tpu.core.
  executor.pad_batch`) so compiled variants are bounded by the bucket
  list, not by every observed batch size.  A bounded staging queue
  between batcher and dispatcher double-buffers: batch N+1 is stacked
  and staged while batch N executes.
* **Deadlines** — a request expired at batch formation or at dispatch
  time completes with :class:`~paddle_tpu.faults.DeadlineExceeded` and is
  never computed.
* **Admission control / load shedding** — the queue is bounded; when
  full, the request with the soonest deadline (the one most likely to
  miss anyway — "oldest deadline first") is rejected with
  :class:`~paddle_tpu.faults.Overloaded`, so the p99 of *admitted*
  requests stays bounded by queue-capacity/throughput instead of every
  request timing out together.  ``shed=False`` + unbounded queue is the
  benchmark's control arm.
* **Circuit breaking** — dispatch failures route through
  ``faults.classify``: retryable ones (transient ``XlaRuntimeError``,
  injected transients) retry per ``retry_policy`` (default: once);
  persistent failures poison only the offending model — after
  ``breaker_threshold`` consecutive failed batches its breaker opens and
  requests fail fast with :class:`~paddle_tpu.faults.ModelUnavailable`
  until a cooldown probe succeeds.  Healthy co-tenants keep serving.
* **Health** — ``warming → ready → draining → stopped``;
  :meth:`Server.health` is the readiness surface.
* **Graceful drain** — :meth:`Server.shutdown` (``drain=True``) closes
  admission (:class:`~paddle_tpu.faults.ServerClosed`), lets the batcher
  and dispatcher finish every admitted request, then joins the threads:
  zero admitted requests are dropped.  The CLI wires SIGTERM to exactly
  this, composing with the PR 6 ``Supervisor`` for relaunch.

Everything is instrumented through the observability registry
(``serving/*`` metrics, frozen in ``METRIC_NAMES``) and the JSONL event
log, and every degradation path has a deterministic fault-injection site
(``serving.request``, ``serving.dispatch``).
"""
from __future__ import annotations

import collections
import logging
import queue as _queue_mod
import threading
import time
from typing import Callable, Dict, List, Optional, Sequence

import numpy as np

from .. import faults as _faults
from .. import observability as obs
from ..core.executor import pad_batch, stack_feeds
from ..core.registry import register_tunable
from ..testing import faultinject as _fi
from ..testing import lockwatch as _lw
from .model import Model

logger = logging.getLogger("paddle_tpu")

# Autotuner knob declaration (paddle_tpu.tuning), next to the batcher it
# controls: max_batch bounds the coalescing window (and the compiled
# bucket list), max_wait_ms trades first-request latency against batch
# fill — the right point depends on model cost per row and offered load.
register_tunable(
    "serving/batcher",
    space={"max_batch": (8, 16, 32, 64), "max_wait_ms": (1.0, 2.0, 5.0,
                                                         10.0)},
    default={"max_batch": 32, "max_wait_ms": 5.0},
    description="serving batcher coalescing policy: maximum batch size "
                "and the wait after the first queued request.")

__all__ = ["Server", "PendingResponse", "ModelError"]

# health states, in lifecycle order
WARMING, READY, DRAINING, STOPPED = "warming", "ready", "draining", "stopped"


class ModelError(RuntimeError):
    """A dispatched batch failed fatally (after any retries); carries the
    underlying error string.  The request was computed-and-lost, not
    shed — distinguish it from the admission-side rejections."""


def _buckets(max_batch: int) -> List[int]:
    """Power-of-two bucket sizes up to (and always including) max_batch."""
    out, b = [], 1
    while b < max_batch:
        out.append(b)
        b *= 2
    out.append(max_batch)
    return out


def _bucket_for(n: int, buckets: Sequence[int]) -> int:
    for b in buckets:
        if n <= b:
            return b
    return buckets[-1]


def _feed_sig(feeds: Dict[str, np.ndarray]):
    return tuple(sorted((k, v.shape, str(v.dtype)) for k, v in feeds.items()))


class PendingResponse:
    """Future-like handle for one admitted request.  Terminal exactly
    once: either ``outputs`` (a list of per-request arrays) or a typed
    error.  ``result()`` blocks; ``add_done_callback`` fires on the
    completing thread (or immediately if already terminal)."""

    __slots__ = ("id", "model", "feeds", "sig", "deadline", "t_admit",
                 "outputs", "error", "span", "dispatch_ms", "_event",
                 "_callbacks", "_lock")

    def __init__(self, req_id, model: str, feeds, deadline: Optional[float]):
        self.id = req_id
        self.model = model
        self.feeds = feeds
        self.sig = _feed_sig(feeds)
        self.deadline = deadline          # time.monotonic() or None
        self.t_admit = time.monotonic()
        self.outputs = None
        self.error: Optional[BaseException] = None
        # model-dispatch wall of the batch that served this request (ms);
        # None for rejected/expired requests.  total latency minus this
        # is the queue/batch/staging wait — the fleet autoscaler's
        # scale-out signal (serving_budget's decomposition, live)
        self.dispatch_ms: Optional[float] = None
        # lifecycle tracing span (one trace per request), started at
        # admission on the submitting thread, ended by _complete on
        # whichever thread completes the request
        self.span = None
        self._event = threading.Event()
        self._callbacks: List[Callable] = []
        self._lock = _lw.make_lock("serving.request")

    def expired(self, now: Optional[float] = None) -> bool:
        return (self.deadline is not None
                and (now if now is not None else time.monotonic())
                >= self.deadline)

    def done(self) -> bool:
        return self._event.is_set()

    def _complete(self, outputs=None, error: Optional[BaseException] = None):
        with self._lock:
            if self._event.is_set():
                return False
            self.outputs = outputs
            self.error = error
            cbs, self._callbacks = self._callbacks, []
            self._event.set()
        obs.observe_hist("serving/request_ms",
                         (time.monotonic() - self.t_admit) * 1e3)
        if self.span is not None:
            self.span.end(status="ok" if error is None
                          else type(error).__name__)
        for cb in cbs:
            try:
                cb(self)
            except Exception:
                logger.exception("serving: response callback failed")
        return True

    def add_done_callback(self, cb: Callable):
        with self._lock:
            if not self._event.is_set():
                self._callbacks.append(cb)
                return
        cb(self)

    def result(self, timeout: Optional[float] = None):
        if not self._event.wait(timeout):
            raise TimeoutError(
                f"request {self.id!r}: no response within {timeout}s")
        if self.error is not None:
            raise self.error
        return self.outputs


class _ModelRuntime:
    """Per-tenant state: admission queue, batcher + dispatcher threads,
    circuit breaker."""

    def __init__(self, model: Model, server: "Server"):
        self.model = model
        self.srv = server
        self.lock = _lw.make_lock("serving.rt")
        self.cond = _lw.make_condition("serving.rt", self.lock)
        self.queue: collections.deque = collections.deque()
        self.staging: _queue_mod.Queue = _queue_mod.Queue(
            maxsize=max(1, server.staging_depth))
        self.batcher: Optional[threading.Thread] = None
        self.dispatcher: Optional[threading.Thread] = None
        self.closed = False               # no more admissions (drain/stop)
        # breaker
        self.consecutive_failures = 0
        self.breaker_open = False
        self.breaker_open_until = 0.0     # monotonic; probe allowed after
        self.served = 0
        self.dispatched_batches = 0

    # -- breaker ------------------------------------------------------------
    def breaker_state(self, now: Optional[float] = None) -> str:
        with self.lock:
            if not self.breaker_open:
                return "closed"
            now = time.monotonic() if now is None else now
            return "half_open" if now >= self.breaker_open_until else "open"

    def _note_batch_failure(self, err: BaseException, span=None):
        opened = False
        with self.lock:
            self.consecutive_failures += 1
            if (self.consecutive_failures >= self.srv.breaker_threshold
                    and not self.breaker_open):
                self.breaker_open = True
                opened = True
            if self.breaker_open:
                self.breaker_open_until = (time.monotonic()
                                           + self.srv.breaker_cooldown_s)
        if opened:
            obs.inc_counter("serving/breaker_open")
            obs.emit_event("serving", event="breaker_open",
                           model=self.model.name,
                           error=f"{type(err).__name__}: {err}")
            if span is not None:
                span.event("breaker_open",
                           error=f"{type(err).__name__}: {err}")
            logger.error("serving: circuit breaker OPEN for model %r "
                         "after %d consecutive failures (%s: %s)",
                         self.model.name, self.consecutive_failures,
                         type(err).__name__, err)

    def _note_batch_success(self, span=None):
        closed = False
        with self.lock:
            self.consecutive_failures = 0
            if self.breaker_open:
                self.breaker_open = False
                closed = True
        if closed:
            obs.emit_event("serving", event="breaker_close",
                           model=self.model.name)
            if span is not None:
                span.event("breaker_close")
            logger.info("serving: circuit breaker closed for model %r "
                        "(probe succeeded)", self.model.name)


class Server:
    """In-process multi-tenant inference server (see module docstring).

    Minimal use::

        srv = Server(max_batch=8, max_wait_ms=2)
        srv.add_model(Model.from_artifact("/path/to/export"))
        srv.start()
        out = srv.infer({"img": example}, timeout=1.0)   # single tenant
        srv.shutdown()                                   # graceful drain

    ``deadline_ms=None`` disables deadlines; ``queue_capacity=None``
    disables admission bounds (with ``shed=False`` this is the
    no-robustness control arm the serving benchmark measures against).
    """

    def __init__(self, max_batch: Optional[int] = None,
                 max_wait_ms: Optional[float] = None,
                 deadline_ms: Optional[float] = 100.0,
                 queue_capacity: Optional[int] = 256,
                 shed: bool = True,
                 breaker_threshold: int = 3,
                 breaker_cooldown_s: float = 30.0,
                 staging_depth: int = 2,
                 retry_policy: Optional[_faults.RetryPolicy] = None,
                 warmup: bool = True,
                 warmup_buckets: Optional[Sequence[int]] = None,
                 autotune: Optional[bool] = None):
        # max_batch/max_wait_ms default to the hand-picked (32, 5.0) —
        # or, under the autotune opt-in (``autotune=True``, else the
        # `autotune` flag), the persisted serving/batcher winner for
        # this host.  Explicit arguments always win.
        if max_batch is None or max_wait_ms is None:
            from ..core.registry import resolve_tuned
            cfg = resolve_tuned("serving/batcher",
                                {"max_batch": 32, "max_wait_ms": 5.0},
                                autotune)
            if max_batch is None:
                max_batch = cfg["max_batch"]
            if max_wait_ms is None:
                max_wait_ms = cfg["max_wait_ms"]
        if max_batch < 1:
            raise ValueError(f"max_batch must be >= 1, got {max_batch}")
        if queue_capacity is not None and queue_capacity < 1:
            raise ValueError(
                f"queue_capacity must be >= 1 or None, got {queue_capacity}")
        if breaker_threshold < 1:
            raise ValueError(
                f"breaker_threshold must be >= 1, got {breaker_threshold}")
        self.max_batch = int(max_batch)
        self.max_wait_s = float(max_wait_ms) / 1e3
        self.default_deadline_ms = deadline_ms
        self.queue_capacity = queue_capacity
        self.shed = bool(shed)
        self.breaker_threshold = int(breaker_threshold)
        self.breaker_cooldown_s = float(breaker_cooldown_s)
        self.staging_depth = int(staging_depth)
        # "transient XlaRuntimeErrors retry once": 2 attempts total
        self.retry_policy = retry_policy if retry_policy is not None else \
            _faults.RetryPolicy(max_attempts=2, backoff_base_s=0.005,
                                backoff_max_s=0.1, seed=0)
        self.buckets = _buckets(self.max_batch)
        self.warmup = bool(warmup)
        self.warmup_buckets = list(warmup_buckets) if warmup_buckets \
            else [self.buckets[0], self.buckets[-1]]
        self._models: Dict[str, _ModelRuntime] = {}
        self._decode: Dict[str, object] = {}   # name -> DecodeRuntime
        self._state = WARMING
        self._state_lock = _lw.make_lock("serving.server.state")
        self._req_counter = 0
        self._started = False

    # -- lifecycle -----------------------------------------------------------
    @property
    def state(self) -> str:
        return self._state

    def _set_state(self, state: str):
        with self._state_lock:
            self._state = state
        obs.emit_event("serving", event="state", state=state)

    def ready(self) -> bool:
        return self._state == READY

    def add_model(self, model: Model):
        if self._started:
            raise RuntimeError("Server.add_model: server already started")
        if model.name in self._models:
            raise ValueError(f"duplicate model name {model.name!r}")
        self._models[model.name] = _ModelRuntime(model, self)

    def add_decode_model(self, engine, name: Optional[str] = None,
                         mode: str = "continuous",
                         step_wait_ms: Optional[float] = None,
                         retry_policy: Optional[_faults.RetryPolicy] = None,
                         autotune: Optional[bool] = None):
        """Mount an incremental-decode slot pool as a tenant: ``engine``
        is a :class:`~paddle_tpu.serving.decode.DecodeEngine`; requests
        go through :meth:`submit_decode`.  The pool inherits the server's
        deadline/shedding/breaker envelope and shares its lifecycle
        (start/drain/shutdown/health)."""
        from .decode import DecodeRuntime   # lazy: decode imports server
        if self._started:
            raise RuntimeError(
                "Server.add_decode_model: server already started")
        pool = DecodeRuntime(
            engine, name=name, mode=mode, step_wait_ms=step_wait_ms,
            default_deadline_ms=self.default_deadline_ms,
            queue_capacity=self.queue_capacity, shed=self.shed,
            breaker_threshold=self.breaker_threshold,
            breaker_cooldown_s=self.breaker_cooldown_s,
            retry_policy=(retry_policy if retry_policy is not None
                          else self.retry_policy),
            autotune=autotune)
        if pool.name in self._models or pool.name in self._decode:
            raise ValueError(f"duplicate model name {pool.name!r}")
        self._decode[pool.name] = pool
        return pool

    def start(self):
        """Warm up every tenant, spawn its batcher/dispatcher pair, flip
        to ready.  Warmup dispatches the model's example at the smallest
        and largest bucket so steady-state requests never pay a compile
        (other buckets compile on first use, tagged cold in telemetry)."""
        if self._started:
            raise RuntimeError("Server.start: already started")
        if not self._models and not self._decode:
            raise ValueError("Server.start: no models added")
        self._started = True
        self._set_state(WARMING)
        for rt in self._models.values():
            if self.warmup and rt.model.example is not None:
                for b in self.warmup_buckets:
                    stacked = pad_batch(
                        stack_feeds([rt.model.example]), b)
                    outs = rt.model(stacked)
                    for o in outs:                     # block: real warmup
                        if o is not None:
                            np.asarray(o)
            rt.batcher = threading.Thread(
                target=self._batch_loop, args=(rt,),
                name=f"pt-serving-batch-{rt.model.name}", daemon=True)
            rt.dispatcher = threading.Thread(
                target=self._dispatch_loop, args=(rt,),
                name=f"pt-serving-dispatch-{rt.model.name}", daemon=True)
            rt.batcher.start()
            rt.dispatcher.start()
        for pool in self._decode.values():
            pool.start(warmup=self.warmup)
        self._set_state(READY)
        return self

    def begin_drain(self):
        """Close admission; keep completing admitted work.  Idempotent."""
        if self._state in (DRAINING, STOPPED):
            return
        self._set_state(DRAINING)
        for rt in self._models.values():
            with rt.cond:
                rt.closed = True
                rt.cond.notify_all()
        for pool in self._decode.values():
            pool.close()

    def shutdown(self, drain: bool = True, timeout: Optional[float] = None):
        """Stop the server.  ``drain=True`` (graceful): admission closes,
        every admitted request completes (results or typed errors), then
        threads join.  ``drain=False``: queued requests complete with
        :class:`~paddle_tpu.faults.ServerClosed` instead of being
        computed; the in-flight batch still finishes."""
        if not self._started:
            self._set_state(STOPPED)
            return
        if not drain:
            # abort queued work first, then drain the (now empty) queues
            self._set_state(DRAINING)
            for rt in self._models.values():
                with rt.cond:
                    rt.closed = True
                    aborted = list(rt.queue)
                    rt.queue.clear()
                    rt.cond.notify_all()
                for r in aborted:
                    r._complete(error=_faults.ServerClosed(
                        "server stopped before this request was dispatched"))
        else:
            self.begin_drain()
        deadline = None if timeout is None else time.monotonic() + timeout
        for rt in self._models.values():
            for t in (rt.batcher, rt.dispatcher):
                if t is None:
                    continue
                t.join(None if deadline is None
                       else max(0.0, deadline - time.monotonic()))
        for pool in self._decode.values():
            pool.shutdown(drain=drain,
                          timeout=None if deadline is None
                          else max(0.0, deadline - time.monotonic()))
        self._set_state(STOPPED)

    # -- admission -----------------------------------------------------------
    def _resolve_model(self, model: Optional[str]) -> _ModelRuntime:
        if model is None:
            if len(self._models) != 1:
                raise ValueError(
                    f"model name required (tenants: "
                    f"{sorted(self._models)})")
            return next(iter(self._models.values()))
        rt = self._models.get(model)
        if rt is None:
            raise ValueError(f"unknown model {model!r} "
                             f"(tenants: {sorted(self._models)})")
        return rt

    def submit(self, feeds: Dict[str, object], model: Optional[str] = None,
               deadline_ms: Optional[float] = -1.0,
               req_id=None, trace_parent=None) -> PendingResponse:
        """Admit one single-example request (feeds carry NO batch axis).

        Returns a :class:`PendingResponse` once admitted.  Admission
        failures raise typed errors immediately: ``ServerClosed``
        (draining/stopped), ``ModelUnavailable`` (breaker open),
        ``Overloaded`` (queue full and this request had the soonest
        deadline).  ``deadline_ms``: per-request override; the default
        sentinel (-1) means the server default, ``None`` means no
        deadline.  ``trace_parent``: a remote caller's extracted trace
        context (``tracing.RemoteParent``) — the request span parents
        onto it instead of starting a fresh trace, joining this
        replica's work to the submitting process's trace.
        """
        rt = self._resolve_model(model)
        if _fi.ENABLED:
            action = _fi.check("serving.request")
            if action is not None:
                if action.startswith("delay"):
                    _, _, ms = action.partition(":")
                    time.sleep((float(ms) if ms else 50.0) / 1e3)
                else:
                    _fi.raise_for(action, "serving.request")
        if req_id is None:
            with self._state_lock:
                self._req_counter += 1
                req_id = self._req_counter
        # one trace per request (ROOT forces it even if the submitting
        # thread is inside some other traced region — unless a remote
        # caller propagated its own context), started BEFORE the
        # admission checks so every typed rejection — ServerClosed,
        # breaker-open ModelUnavailable, feed-validation errors,
        # Overloaded shedding — reaches the log with its status; those
        # rejections are exactly what an overload trace needs to show.
        # The span ends at the terminal completion, or here on a
        # rejection raise.
        sp = obs.tracing.start_span(
            "serving/request",
            parent=trace_parent if trace_parent is not None
            else obs.tracing.ROOT,
            model=rt.model.name, id=req_id)
        try:
            if self._state != READY:
                raise _faults.ServerClosed(
                    f"server is {self._state}; admission closed")
            if rt.breaker_state() == "open":
                raise _faults.ModelUnavailable(
                    f"model {rt.model.name!r}: circuit breaker open "
                    f"(repeated fatal dispatch errors); retry after "
                    f"cooldown")
            if deadline_ms == -1.0:
                deadline_ms = self.default_deadline_ms
            now = time.monotonic()
            deadline = None if deadline_ms is None \
                else now + deadline_ms / 1e3
            req = PendingResponse(req_id, rt.model.name,
                                  rt.model.coerce_feeds(feeds), deadline)
            req.span = sp
            return self._admit(rt, req)
        except BaseException as e:
            sp.end(status=type(e).__name__)
            raise

    def _admit(self, rt: _ModelRuntime, req: PendingResponse):
        shed_req = None
        with rt.cond:
            if rt.closed:
                raise _faults.ServerClosed(
                    f"server is {self._state}; admission closed")
            if (self.queue_capacity is not None
                    and len(rt.queue) >= self.queue_capacity):
                if not self.shed:
                    # bounded queue without shedding: plain backpressure —
                    # reject the newcomer
                    obs.inc_counter("serving/shed")
                    obs.emit_event("serving", event="shed",
                                   model=rt.model.name, victim="incoming")
                    raise _faults.Overloaded(
                        f"model {rt.model.name!r}: queue full "
                        f"({self.queue_capacity})")
                # oldest-deadline-first: shed whoever is most likely to
                # miss — the soonest deadline among queued + incoming.
                # Deadline-less requests are never preferred as victims;
                # when NOBODY has a deadline this degrades to rejecting
                # the newcomer (plain backpressure).
                victim = min(
                    [r for r in list(rt.queue) + [req]
                     if r.deadline is not None],
                    key=lambda r: r.deadline,
                    default=req)
                if victim is req:
                    obs.inc_counter("serving/shed")
                    obs.emit_event("serving", event="shed",
                                   model=rt.model.name, victim="incoming")
                    raise _faults.Overloaded(
                        f"model {rt.model.name!r}: queue full "
                        f"({self.queue_capacity}) and this request has "
                        f"the soonest deadline")
                rt.queue.remove(victim)
                shed_req = victim
                rt.queue.append(req)
                rt.cond.notify()
            else:
                rt.queue.append(req)
                rt.cond.notify()
        if shed_req is not None:
            obs.inc_counter("serving/shed")
            obs.emit_event("serving", event="shed", model=rt.model.name,
                           victim="queued")
            shed_req._complete(error=_faults.Overloaded(
                f"model {rt.model.name!r}: shed under overload "
                f"(oldest deadline first)"))
        obs.inc_counter("serving/requests")
        return req

    def infer(self, feeds: Dict[str, object], model: Optional[str] = None,
              deadline_ms: Optional[float] = -1.0,
              timeout: Optional[float] = None):
        """Synchronous submit+wait; raises the typed error on rejection."""
        return self.submit(feeds, model=model,
                           deadline_ms=deadline_ms).result(timeout)

    def submit_decode(self, tokens, max_new_tokens: int,
                      model: Optional[str] = None,
                      deadline_ms: Optional[float] = -1.0,
                      req_id=None) -> PendingResponse:
        """Admit one generate request to a decode slot pool (see
        ``add_decode_model``).  Completes with ``{"tokens", "finish",
        "ttft_ms", "inter_token_ms"}``; admission rejections raise the
        same typed errors as :meth:`submit`."""
        if model is None:
            if len(self._decode) != 1:
                raise ValueError(
                    f"decode model name required (decode tenants: "
                    f"{sorted(self._decode)})")
            pool = next(iter(self._decode.values()))
        else:
            pool = self._decode.get(model)
            if pool is None:
                raise ValueError(
                    f"unknown decode model {model!r} (decode tenants: "
                    f"{sorted(self._decode)})")
        if self._state != READY:
            raise _faults.ServerClosed(
                f"server is {self._state}; admission closed")
        if deadline_ms == -1.0:
            deadline_ms = self.default_deadline_ms
        return pool.submit(tokens, max_new_tokens,
                           deadline_ms=deadline_ms, req_id=req_id)

    # -- health --------------------------------------------------------------
    def health(self) -> dict:
        models = {}
        for name, rt in self._models.items():
            with rt.lock:
                depth = len(rt.queue)
                served = rt.served
                batches = rt.dispatched_batches
            models[name] = {
                "breaker": rt.breaker_state(),
                "queue_depth": depth,
                "served": served,
                "batches": batches,
            }
        out = {"state": self._state, "ready": self.ready(),
               "models": models}
        if self._decode:
            out["decode"] = {name: pool.health()
                             for name, pool in self._decode.items()}
        return out

    # -- batcher -------------------------------------------------------------
    def _expire(self, req: PendingResponse, where: str) -> bool:
        """Complete an expired request with DeadlineExceeded; True if it
        was expired.  Never dispatched, never computed."""
        if not req.expired():
            return False
        obs.inc_counter("serving/deadline_expired")
        obs.emit_event("serving", event="deadline_expired",
                       model=req.model, where=where)
        req._complete(error=_faults.DeadlineExceeded(
            f"request {req.id!r}: deadline expired before {where}"))
        return True

    def _batch_loop(self, rt: _ModelRuntime):
        """Coalesce queued requests into staged batches until drained."""
        try:
            while True:
                with rt.cond:
                    while not rt.queue and not rt.closed:
                        rt.cond.wait(timeout=0.1)
                    if not rt.queue and rt.closed:
                        break
                    obs.observe_hist("serving/queue_depth", len(rt.queue))
                    first = rt.queue.popleft()
                if self._expire(first, "batching"):
                    continue
                batch = [first]
                wait_until = time.monotonic() + self.max_wait_s
                while len(batch) < self.max_batch:
                    with rt.cond:
                        # only same-signature requests can stack; others
                        # stay queued, order preserved
                        got = mismatched = None
                        for r in rt.queue:
                            if r.sig == first.sig:
                                got = r
                                break
                            mismatched = r
                        if got is not None:
                            rt.queue.remove(got)
                    if got is not None:
                        if not self._expire(got, "batching"):
                            batch.append(got)
                        continue
                    if mismatched is not None:
                        # a different signature is waiting: ship what we
                        # have now and start its batch next iteration
                        break
                    if rt.closed:       # draining: no waiting for stragglers
                        break
                    remaining = wait_until - time.monotonic()
                    if remaining <= 0:
                        break
                    with rt.cond:
                        if not rt.queue:
                            rt.cond.wait(timeout=remaining)
                live = [r for r in batch
                        if not self._expire(r, "batching")]
                if not live:
                    continue
                stacked = stack_feeds([r.feeds for r in live])
                padded = pad_batch(stacked,
                                   _bucket_for(len(live), self.buckets))
                rt.staging.put((live, padded))
        except BaseException:
            logger.exception("serving: batcher for model %r died",
                             rt.model.name)
        finally:
            rt.staging.put(None)        # dispatcher drain sentinel

    # -- dispatcher ----------------------------------------------------------
    def _dispatch_batch(self, rt: _ModelRuntime, padded, span=None):
        """One model call through the injection site + retry rim."""
        def attempt():
            if _fi.ENABLED:
                action = _fi.check("serving.dispatch")
                if action is not None:
                    if action == "fatal":
                        raise _faults.InjectedFault(
                            "injected fatal fault at serving.dispatch")
                    _fi.raise_for(action, "serving.dispatch")
            return rt.model(padded)

        def on_retry(i, e, d):
            obs.inc_counter("fault/retries")
            obs.emit_event("fault", event="retry", site="serving.dispatch",
                           attempt=i + 1, delay_s=round(d, 4),
                           error=f"{type(e).__name__}: {e}")
            if span is not None:
                span.event("retry", attempt=i + 1, delay_s=round(d, 4),
                           error=f"{type(e).__name__}: {e}")

        if self.retry_policy is None:
            return attempt()
        return _faults.retry_call(
            attempt, self.retry_policy,
            what=f"serving dispatch [{rt.model.name}]", on_retry=on_retry)

    def _dispatch_loop(self, rt: _ModelRuntime):
        while True:
            item = rt.staging.get()
            if item is None:
                break
            live, padded = item
            try:
                self._dispatch_one(rt, live, padded)
            except BaseException as e:   # noqa: BLE001 — containment:
                # a dispatcher death would wedge the staging queue, block
                # the batcher forever and hang shutdown(drain=True); any
                # request this batch carried gets a terminal error instead
                logger.exception("serving: dispatcher for model %r hit an "
                                 "unexpected error", rt.model.name)
                err = ModelError(
                    f"model {rt.model.name!r}: internal dispatch error "
                    f"({type(e).__name__}: {e})")
                for r in live:
                    r._complete(error=err)

    def _dispatch_one(self, rt: _ModelRuntime, live, padded):
        # deadline re-check at the dispatch rim: staging adds wait
        rows = [(i, r) for i, r in enumerate(live)
                if not self._expire(r, "dispatch")]
        if not rows:
            return
        if rt.breaker_state() == "open":
            for _, r in rows:
                r._complete(error=_faults.ModelUnavailable(
                    f"model {rt.model.name!r}: circuit breaker open"))
            return
        bucket = next((int(v.shape[0]) for v in padded.values()), 0)
        # batch span: its OWN trace (a batch is a join point, not a
        # child of any single request), linking every member request's
        # trace by id — retry attempts and breaker transitions attach as
        # span events, so a degraded batch's story reads in one record
        bsp = obs.tracing.start_span(
            "serving/batch", parent=obs.tracing.ROOT,
            model=rt.model.name, size=len(rows), bucket=bucket,
            requests=[r.id for _, r in rows],
            traces=[r.span.trace_id for _, r in rows
                    if r.span is not None])
        t0 = time.monotonic()
        try:
            outs = self._dispatch_batch(rt, padded, span=bsp)
            # materialize + split INSIDE the failure rim: a model whose
            # outputs are not row-wise indexable (scalar fetch, ragged
            # return) is a model failure, not a server crash
            split = [[None if o is None else np.asarray(o[i])
                      for o in outs] for i, _ in rows]
        except BaseException as e:
            rt._note_batch_failure(e, span=bsp)
            err = ModelError(
                f"model {rt.model.name!r}: dispatch failed "
                f"({type(e).__name__}: {e})")
            obs.emit_event("serving", event="error",
                           model=rt.model.name,
                           error=f"{type(e).__name__}: {e}")
            for _, r in rows:
                r._complete(error=err)
            bsp.end(status=type(e).__name__)
            return
        dispatch_ms = (time.monotonic() - t0) * 1e3
        rt._note_batch_success(span=bsp)
        obs.inc_counter("serving/batches")
        obs.observe_hist("serving/batch_size", len(rows))
        with rt.lock:
            rt.dispatched_batches += 1
            rt.served += len(rows)
        obs.emit_event("serving", event="batch", model=rt.model.name,
                       size=len(rows), bucket=bucket,
                       dispatch_ms=round(dispatch_ms, 3))
        for (_, r), out in zip(rows, split):
            r.dispatch_ms = dispatch_ms
            r._complete(outputs=out)
        bsp.end(status="ok", dispatch_ms=round(dispatch_ms, 3))

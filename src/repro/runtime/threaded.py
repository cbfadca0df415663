"""Real threaded actor runtime — actors on OS threads with FIFO mailboxes.

This is the paper's Fig 7 implementation for the *host side* of the JAX
program: data loading, preprocessing, host-to-device staging and step issue
run as actors on dedicated OS threads (one per "hardware queue"), with the
same req/ack + register-quota protocol as the simulator. Because the quota is
enforced, a fast producer (data loader) is back-pressured instead of buffering
unboundedly (§4.3) — this is what `repro.data.pipeline` builds on.

Two pieces live here:

* :class:`_LocalEngine` — drives the *local subset* of an actor graph on OS
  threads. With every key local it IS the threaded runtime's engine; each
  :class:`repro.runtime.process.ProcessRuntime` worker runs one over its own
  node's keys, with cross-node messages diverted through ``send_remote``.
* :class:`ThreadedRuntime` — the :class:`repro.runtime.base.Runtime`
  implementation executors use in-process. Persistent: one instance runs
  many epochs (steps/rounds); actors reset at the *start* of each run so
  their counters stay inspectable afterwards.

Completion is event-driven, not polled. Each engine keeps two lock-protected
counters: ``pending`` (remaining fires of local bounded actors) and ``live``
(local out-register instances not yet fully acked). Both are updated
*before* any ack/req from a fire is posted, so "both zero" (quiescence) can
never be observed while a local actor still owes the graph a message: an
unsent ack means the producer's register is still refcounted, which keeps
the producer's ``live`` non-zero. When every key is local, quiescence is
exactly completion; across processes it feeds the termination protocol in
:mod:`repro.runtime.process`.

Each epoch's start and end (``runtime.epoch_start``, ``runtime.epoch_end``)
and every fire (``actor.fire``, with ``actor``, ``epoch`` and ``fire``, the
actor's version before it) are ``jax.profiler.TraceAnnotation`` spans:
free while no trace records, and on the device trace's clock while one
does.
"""
from __future__ import annotations

import collections
import queue
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Set, Tuple

from repro.runtime.actor import Actor, ActorSpec, build_actors
from repro.runtime.base import Runtime, _check_epoch_names
from repro.runtime.messages import Req, node_of, thread_of


def _no_remote(msg) -> None:
    raise RuntimeError(
        f"message for non-local actor {msg.dst:#x} but no remote transport "
        "is attached (send_remote hook unset)")


class _LocalEngine:
    """Drive the local (node, thread) keys of an actor graph on OS threads.

    All actors are *built* (IDs and consumer wiring need the whole graph)
    but only those on ``local_keys`` are run; a message addressed off-node
    goes through the ``send_remote`` hook. Owners attach:

    * ``send_remote(msg)`` — deliver a Req/Ack to a non-local key
    * ``on_output(name, value, version)`` — a collected actor emitted
    * ``on_quiescence(flag)`` — local quiescence changed (called under the
      counter lock, so reports are emitted in transition order)
    * ``on_error(exc, key)`` — a worker thread raised
    """

    def __init__(self, specs: Sequence[ActorSpec],
                 local_keys: Optional[Sequence[Tuple[int, int]]] = None):
        self.specs = list(specs)
        self.by_name, self.by_id = build_actors(self.specs)
        all_keys = sorted({(s.node, s.thread) for s in self.specs})
        if local_keys is None:
            self.local_keys = all_keys
        else:
            wanted = set(local_keys)
            self.local_keys = [k for k in all_keys if k in wanted]
        local = set(self.local_keys)
        self.local_actors: List[Actor] = [
            a for a in self.by_name.values()
            if (a.spec.node, a.spec.thread) in local]
        self.actors_on: Dict[Tuple[int, int], List[Actor]] = \
            collections.defaultdict(list)
        for a in self.local_actors:
            self.actors_on[(a.spec.node, a.spec.thread)].append(a)
        # hooks
        self.send_remote: Callable[[Any], None] = _no_remote
        self.on_output: Optional[Callable[[str, Any, int], None]] = None
        self.on_quiescence: Optional[Callable[[bool], None]] = None
        self.on_error: Optional[Callable[[BaseException, Tuple[int, int]], None]] = None
        self.collect_names: Set[str] = set()
        # optional chaos layer (repro.runtime.chaos.FaultInjector): consulted
        # before every local fire and for every outgoing message
        self.fault_injector = None
        # optional repro.analysis.trace.TraceRecorder: records every Req
        # delivery (version + what the resequencer released) so the static
        # trace sanitizer can certify the run restored canonical order
        self.trace_recorder = None
        # epoch state
        self._epoch = 0
        self._mailboxes: Dict[Tuple[int, int], queue.Queue] = {}
        self._threads: List[threading.Thread] = []
        self._lock = threading.Lock()
        self._pending = 0
        self._live = 0
        self._quiescent = True
        self._stopping = False
        self._t0 = time.perf_counter()

    # -- epoch lifecycle ---------------------------------------------------------
    def start_epoch(self, ctx: Optional[Dict[str, Any]] = None,
                    fires: Optional[Dict[str, int]] = None) -> None:
        """Reset local actors and launch one worker thread per local key.

        ``fires`` overrides per-actor fire bounds for this epoch only;
        ``ctx`` is routed to each actor's ``on_epoch`` hook (hooks with no
        entry still run with ``None`` so per-epoch state resets happen)."""
        from jax.profiler import TraceAnnotation

        ctx = ctx or {}
        fires = fires or {}
        self._epoch += 1
        with TraceAnnotation("runtime.epoch_start"):
            if self.trace_recorder is not None:
                # resequencer state resets per epoch; the trace sanitizer
                # checks canonical order per (epoch, consumer, channel)
                self.trace_recorder.current_epoch = self._epoch
            self._stopping = False
            for a in self.local_actors:
                a.reset(max_fires=fires.get(a.spec.name))
            # hooks run after every reset: an on_epoch that seeds an upstream
            # cell must not race a half-reset consumer
            for a in self.local_actors:
                if a.spec.on_epoch is not None:
                    a.spec.on_epoch(ctx.get(a.spec.name))
            pending = sum(a.max_fires - a.fired for a in self.local_actors
                          if a.max_fires is not None)
            # fresh mailboxes per epoch: anything a previous (timed-out) epoch
            # left queued is unreachable garbage, not a poisoned message
            self._mailboxes = {k: queue.Queue() for k in self.local_keys}
            self._t0 = time.perf_counter()
            with self._lock:
                self._pending = pending
                self._live = 0
                self._quiescent = (pending == 0)
                if self.on_quiescence is not None:
                    self.on_quiescence(self._quiescent)
            self._threads = []
            epoch = self._epoch
            for key in self.local_keys:
                t = threading.Thread(target=self._worker, args=(key, epoch),
                                     daemon=True)
                t.start()
                self._threads.append(t)

    def stop_workers(self) -> None:
        self._stopping = True
        for box in self._mailboxes.values():
            box.put(None)

    def join_workers(self, timeout: float = 2.0) -> None:
        for t in self._threads:
            t.join(timeout=timeout)

    def snapshot(self):
        """(history, peak_regs, edge_bytes, fired) of the local actors."""
        hist = {a.spec.name: list(a.history) for a in self.local_actors}
        peaks = {a.spec.name: a.peak_regs_in_use for a in self.local_actors}
        edges = {(a.spec.name, cname): n for a in self.local_actors
                 for cname, n in a.edge_bytes.items()}
        fired = {a.spec.name: a.fired for a in self.local_actors}
        return hist, peaks, edges, fired

    # -- message routing ---------------------------------------------------------
    def post(self, msg) -> None:
        """Route an *originating* message (the sending engine's side).

        With a fault injector attached the message may be delayed,
        duplicated or dropped here; :meth:`deliver` is the fault-free path
        used for messages arriving from another process (already routed at
        their sender)."""
        inj = self.fault_injector
        if inj is None:
            self.deliver(msg)
            return
        src = self.by_id[msg.src].spec.name
        dst = self.by_id[msg.dst].spec.name
        epoch, boxes = self._epoch, self._mailboxes
        for m, delay in inj.route(msg, src, dst):
            if delay > 0:
                t = threading.Timer(delay, self._deliver_late,
                                    args=(m, epoch, boxes))
                t.daemon = True
                t.start()
            else:
                self.deliver(m)

    def deliver(self, msg) -> None:
        box = self._mailboxes.get((node_of(msg.dst), thread_of(msg.dst)))
        if box is not None:
            box.put(msg)
        else:
            self.send_remote(msg)

    def _deliver_late(self, msg, epoch: int, boxes) -> None:
        """Timer callback for a delayed message. A pending delayed Req/Ack
        keeps its producer's register referenced, so the epoch cannot
        conclude before delivery; if the epoch was nevertheless abandoned
        (timeout/error), deliver into the *captured* mailbox table — a
        stale epoch's boxes are unreachable garbage, never poison."""
        if self._epoch != epoch or self._stopping:
            return
        box = boxes.get((node_of(msg.dst), thread_of(msg.dst)))
        if box is not None:
            box.put(msg)
        else:
            self.send_remote(msg)

    # -- counters ----------------------------------------------------------------
    def _bump(self, dpending: int, dlive: int) -> None:
        with self._lock:
            self._pending += dpending
            self._live += dlive
            q = (self._pending == 0 and self._live == 0)
            if q != self._quiescent:
                self._quiescent = q
                if self.on_quiescence is not None:
                    self.on_quiescence(q)

    @property
    def quiescent(self) -> bool:
        with self._lock:
            return self._quiescent

    # -- worker loop -------------------------------------------------------------
    def _worker(self, key: Tuple[int, int], epoch: int) -> None:
        box = self._mailboxes[key]
        try:
            self._fire_ready(key, epoch)
            while True:
                msg = box.get()
                if msg is None or self._epoch != epoch:
                    return
                actor = self.by_id[msg.dst]
                if isinstance(msg, Req):
                    rec = self.trace_recorder
                    if rec is None:
                        actor.on_req(msg)
                    else:
                        self._traced_on_req(rec, actor, msg)
                else:
                    if actor.on_ack(msg):
                        self._bump(0, -1)
                self._fire_ready(key, epoch)
        except BaseException as e:  # surface worker crashes to the owner
            self._stopping = True
            if self.on_error is not None:
                self.on_error(e, key)
            self.stop_workers()

    @staticmethod
    def _traced_on_req(rec, actor: Actor, msg: Req) -> None:
        """Deliver a Req through the resequencer while recording what it
        did: the versions released to the FIFO (empty for a buffered early
        arrival) and whether the message was accepted at all (duplicates
        are dropped without an ack)."""
        ch = msg.channel
        before = actor.in_next.get(ch)
        if before is None:                      # undeclared channel: FIFO
            actor.on_req(msg)
            rec.record_delivery(actor.spec.name, ch, msg.version,
                                (msg.version,), 1)
            return
        pend_before = len(actor.in_pending[ch])
        actor.on_req(msg)
        stride = actor.in_stride[ch]
        released = tuple(range(before, actor.in_next[ch], stride))
        accepted = bool(released) or len(actor.in_pending[ch]) > pend_before
        rec.record_delivery(actor.spec.name, ch, msg.version, released,
                            stride, accepted)

    def _fire_ready(self, key: Tuple[int, int], epoch: int) -> None:
        from jax.profiler import TraceAnnotation

        progressed = True
        while progressed and not self._stopping:
            progressed = False
            for actor in self.actors_on[key]:
                while (actor.ready() and not self._stopping
                       and self._epoch == epoch):
                    if self.fault_injector is not None:
                        # may raise WorkerKilled (threads) or hard-exit the
                        # process (a KillWorker fault)
                        self.fault_injector.before_fire(actor.spec.name)
                    start = time.perf_counter() - self._t0
                    with TraceAnnotation("actor.fire", actor=actor.spec.name,
                                         epoch=epoch, fire=actor.version):
                        out, acks, reg_id = actor.fire()
                    # wall-clock action history mirrors the simulator's, so
                    # pipeline overlap can be observed on real threads too
                    actor.history.append((start, time.perf_counter() - self._t0))
                    version = actor.version - 1
                    # collect only fires the protocol emitted (emit_every
                    # suppresses all but each k-th output of an acc actor).
                    # Outputs report BEFORE the counter bump: on a shared
                    # FIFO channel the epoch's last output then provably
                    # precedes the quiescent-transition report.
                    if (actor.spec.name in self.collect_names
                            and actor.emitted_last_fire
                            and self.on_output is not None):
                        self.on_output(actor.spec.name, out, version)
                    # counters move BEFORE the fire's messages go out —
                    # completion must be unobservable while acks are unsent
                    self._bump(-1 if actor.max_fires is not None else 0,
                               1 if reg_id != -1 else 0)
                    for ack in acks:
                        self.post(ack)
                    if reg_id != -1:
                        for req in actor.emit_reqs(out, reg_id, version):
                            self.post(req)
                    progressed = True


class ThreadedRuntime(Runtime):
    """Drive a graph of :class:`ActorSpec`s on OS threads, in-process.

    ``collect_outputs_of`` names the actor(s) whose outputs :meth:`run`
    returns: a single name yields a flat list (fire order), a sequence of
    names yields ``{name: [outputs...]}`` — the training pipeline collects
    the loss stream and every optimizer actor at once.

    Persistent: one instance serves many :meth:`run` epochs. Actors reset at
    the *start* of the next run, so ``by_name`` counters (fired, out_counter,
    peak_regs_in_use) remain inspectable after a run — the zero-consumer and
    data-pipeline tests rely on that.
    """

    def __init__(self, specs: Sequence[ActorSpec],
                 collect_outputs_of=None, faults=None, trace=None):
        self._engine = _LocalEngine(specs)
        if faults is not None:
            from repro.runtime.chaos import FaultInjector
            self._engine.fault_injector = FaultInjector(faults)
        if trace is not None:
            # a repro.analysis.trace.TraceRecorder; the injector (if any)
            # also reports which faults it actually applied
            self._engine.trace_recorder = trace
            if self._engine.fault_injector is not None:
                self._engine.fault_injector.recorder = trace
        self.by_name = self._engine.by_name
        self.by_id = self._engine.by_id
        self._collect_single = (collect_outputs_of is None
                                or isinstance(collect_outputs_of, str))
        names = ([collect_outputs_of] if self._collect_single else
                 list(collect_outputs_of))
        self._collect_names = {n for n in names if n is not None}
        self._engine.collect_names = self._collect_names
        self._engine.on_output = self._on_output
        self._engine.on_quiescence = self._on_quiescence
        self._engine.on_error = self._on_error
        self.outputs: List[Any] = []
        self.outputs_by_name: Dict[str, List[Any]] = {
            n: [] for n in self._collect_names}
        self._outputs_lock = threading.Lock()
        self._wake = threading.Event()
        self._errors: List[Tuple[BaseException, Tuple[int, int]]] = []
        self.last_history: Dict[str, List[Tuple[float, float]]] = {}
        self.last_peak_regs: Dict[str, int] = {}
        self.last_edge_bytes: Dict[Tuple[str, str], int] = {}
        self.last_fired: Dict[str, int] = {}

    # -- engine hooks ------------------------------------------------------------
    def _on_output(self, name: str, value: Any, version: int) -> None:
        with self._outputs_lock:
            self.outputs_by_name[name].append(value)
            if self._collect_single:
                self.outputs.append(value)

    def _on_quiescence(self, q: bool) -> None:
        if q:
            self._wake.set()

    def _on_error(self, exc: BaseException, key: Tuple[int, int]) -> None:
        self._errors.append((exc, key))
        self._wake.set()

    # -- public API --------------------------------------------------------------
    def run(self, ctx: Optional[Dict[str, Any]] = None,
            fires: Optional[Dict[str, int]] = None,
            timeout: float = 120.0):
        """Run one epoch until every bounded actor has exhausted its fires.

        ``ctx`` feeds per-actor ``on_epoch`` hooks (per-step batches, params
        to load, a serve round's work list); ``fires`` overrides fire bounds
        for this epoch. Returns the collected outputs: a flat list when a
        single actor name was given, else ``{name: [outputs...]}``.
        """
        from jax.profiler import TraceAnnotation

        _check_epoch_names(self._engine.specs, ctx, fires)
        fires = fires or {}
        effective = {s.name: fires.get(s.name, s.max_fires)
                     for s in self._engine.specs}
        if not any(v is not None for v in effective.values()):
            raise ValueError("threaded runtime needs at least one bounded actor")
        self.outputs = []
        self.outputs_by_name = {n: [] for n in self._collect_names}
        self._errors = []
        self._wake.clear()
        self._engine.start_epoch(ctx, fires)
        deadline = time.monotonic() + timeout
        while True:
            if self._errors or self._engine.quiescent:
                break
            remaining = deadline - time.monotonic()
            if remaining <= 0:
                break
            self._wake.wait(remaining)
            self._wake.clear()
        with TraceAnnotation("runtime.epoch_end"):
            self._engine.stop_workers()
            self._engine.join_workers(2.0)
            (self.last_history, self.last_peak_regs,
             self.last_edge_bytes, self.last_fired) = self._engine.snapshot()
        if self._errors:
            exc, key = self._errors[0]
            if hasattr(exc, "add_note"):  # py3.11+
                exc.add_note(f"raised in actor worker thread "
                             f"(node={key[0]}, thread={key[1]})")
            # re-raise with the worker thread's original traceback attached
            raise exc
        bounded = [a for a in self._engine.local_actors
                   if a.max_fires is not None]
        if not all(a.exhausted for a in bounded):
            raise TimeoutError(
                "threaded actor runtime did not complete: "
                + ", ".join(f"{a.spec.name}={a.fired}/{a.max_fires}"
                            for a in bounded if not a.exhausted))
        return self.outputs if self._collect_single else self.outputs_by_name

    def close(self) -> None:
        self._engine.stop_workers()
        self._engine.join_workers(0.5)

"""Multi-lane horizontal-fusion executor (QRMark §6.2, system layer).

The paper's resource-aware multi-channel horizontal fusion assigns more
CUDA streams to GPU-intensive pipeline stages.  The host-side analogue
implemented here is an explicit *stage graph*: each detection stage
(ingest/preprocess, tiled decode, RS correction) is a :class:`Stage`
with a declared resource profile, and :class:`LaneExecutor` runs the
allocator's lane assignment as real concurrency — ``lanes[k]`` worker
threads per stage k, connected by bounded queues, with multiple
mini-batches in flight per stage.  Stage functions that dispatch jitted
JAX computations return *futures* (async dispatch), so a downstream
stage enqueues device work while upstream lanes keep feeding — the
N-lane generalisation of the 2-deep ``PrefetchIterator`` this module
replaces (``interleave.PrefetchIterator`` is now a single-stage
``LaneExecutor``).

Correctness contract: results come out in *input order* regardless of
lane count, and stage functions are pure w.r.t. their payload (all RNG
keys are pre-derived from the item's sequence number), so any lane
configuration is bit-identical to serial execution of the same stage
functions.

Two execution modes share the same worker machinery:

* :meth:`LaneExecutor.run` — the original single-use, stream-terminated
  generator (offline batch jobs: the whole input is known up front and
  results are consumed in order);
* **service mode** (:meth:`LaneExecutor.start`) — a long-lived executor
  for online serving: :meth:`submit` enqueues one payload and returns a
  :class:`Ticket` (a future), completions are delivered *out of order*
  as they finish (per-ticket callback + ``Ticket.result()``),
  :meth:`drain` waits for in-flight work, :meth:`close` shuts down, and
  :meth:`reconfigure` re-applies a new lane allocation *live* — workers
  are added or retired without dropping queued work, so Algorithm 1 can
  be re-run online when measured stage latencies drift from warmup.
"""
from __future__ import annotations

import dataclasses
import queue
import threading
from typing import Any, Callable, Dict, Iterable, Iterator, List, Optional, \
    Sequence

from repro.core import spans


@dataclasses.dataclass
class Stage:
    """One node of the detection stage graph.

    ``fn`` maps payload -> payload.  ``lanes`` is the number of worker
    threads (concurrent mini-batches in flight for this stage); ``depth``
    bounds the stage's input queue.  ``gpu_intensive`` records the
    resource profile the allocator uses to decide who gets extra lanes
    (Algorithm 1 gives device-bound stages more streams, host-bound
    stages fewer)."""
    name: str
    fn: Callable[[Any], Any]
    lanes: int = 1
    depth: int = 2
    gpu_intensive: bool = False
    profile: Optional[object] = None   # allocator.StageProfile when known

    def __post_init__(self):
        if self.lanes < 1:
            raise ValueError(f"stage {self.name!r}: lanes must be >= 1")
        if self.depth < 1:
            raise ValueError(f"stage {self.name!r}: depth must be >= 1")


class _Failure:
    """Error marker that flows through the graph in place of a payload so
    ordering never stalls; re-raised at the consumer in sequence order."""

    def __init__(self, err: BaseException):
        self.err = err


_DONE = object()


class _Retire:
    """Poison token for live lane removal: the service worker that pops
    it exits instead of processing — queued payloads behind it keep
    flowing through the stage's remaining lanes."""


class Ticket:
    """Future for one payload submitted to a service-mode executor.

    Resolved (out of input order — completion order) by the dispatcher
    thread; ``result()`` re-raises the stage error if the payload
    failed."""

    def __init__(self, seq: int):
        self.seq = seq
        self._ready = threading.Event()
        self._value: Any = None
        self._error: Optional[BaseException] = None

    def done(self) -> bool:
        return self._ready.is_set()

    def result(self, timeout: Optional[float] = None):
        if not self._ready.wait(timeout):
            raise TimeoutError(f"ticket {self.seq} not done after "
                               f"{timeout}s")
        if self._error is not None:
            raise self._error
        return self._value

    def exception(self, timeout: Optional[float] = None
                  ) -> Optional[BaseException]:
        if not self._ready.wait(timeout):
            raise TimeoutError(f"ticket {self.seq} not done after "
                               f"{timeout}s")
        return self._error

    def _resolve(self, value):
        self._value = value
        self._ready.set()

    def _reject(self, err: BaseException):
        self._error = err
        self._ready.set()


class LaneExecutor:
    """Runs a linear stage graph over a stream of items.

    * one input queue per stage, ``maxsize = stage.depth`` — bounded
      buffering is what overlaps the stages without unbounded memory;
    * ``stage.lanes`` daemon worker threads per stage — horizontal
      fusion: several mini-batches of the *same* stage in flight;
    * a reorder buffer at the sink restores input order, so lane count
      never changes observable results.
    """

    def __init__(self, stages: Sequence[Stage], name: str = "pipeline"):
        if not stages:
            raise ValueError("LaneExecutor needs at least one stage")
        self.stages = list(stages)
        self.name = name
        self._cancel = threading.Event()
        self._used = False
        # service-mode state (populated by start())
        self._service = False
        self._closed = False
        self._lock = threading.Lock()
        self._idle = threading.Condition(self._lock)
        self._tickets: Dict[int, tuple] = {}   # seq -> (Ticket, callback)
        self._submit_seq = 0
        self._service_threads: List[threading.Thread] = []
        self._lane_counts: Dict[str, int] = {}

    # -- cooperative queue ops so close() can unstick blocked workers;
    # the time they block is a "wait.queue" span ------------------------
    def _put(self, q: "queue.Queue", item) -> bool:
        if self._cancel.is_set():
            return False
        try:
            q.put_nowait(item)
            return True
        except queue.Full:
            pass
        with spans.span("wait.queue"):
            while not self._cancel.is_set():
                try:
                    q.put(item, timeout=0.05)
                    return True
                except queue.Full:
                    continue
        return False

    def _get(self, q: "queue.Queue"):
        if self._cancel.is_set():
            return _DONE
        try:
            return q.get_nowait()
        except queue.Empty:
            pass
        with spans.span("wait.queue"):
            while not self._cancel.is_set():
                try:
                    return q.get(timeout=0.05)
                except queue.Empty:
                    continue
        return _DONE

    @staticmethod
    def _call(stage: Stage, payload, item: int):
        """``stage.fn(payload)`` as a ``stage.<name>`` span; an error
        becomes the payload's :class:`_Failure`."""
        with spans.span("stage." + stage.name, item=item):
            try:
                return stage.fn(payload)
            except BaseException as e:
                return _Failure(e)

    def close(self):
        """Cancel in-flight work (workers drain and exit).  In service
        mode also rejects every unresolved ticket so no caller blocks on
        a result that will never arrive; call :meth:`drain` first for a
        graceful shutdown."""
        with self._lock:
            self._closed = True
            pending = list(self._tickets.values())
            self._tickets.clear()
            self._idle.notify_all()
        self._cancel.set()
        for ticket, callback in pending:
            self._deliver_rejection(ticket, callback)
        # join service threads: cancelled workers exit within one poll
        # interval, and leaving them alive into interpreter shutdown
        # aborts the process when the runtime's C++ state is torn down
        # under a thread mid-teardown
        me = threading.current_thread()
        for t in self._service_threads:
            if t is not me:
                t.join(timeout=2.0)

    # ------------------------------------------------------------------
    # service mode: long-lived submit/complete executor
    # ------------------------------------------------------------------
    def start(self) -> "LaneExecutor":
        """Switch to long-lived service mode.

        Spawns the stage workers and a dispatcher thread; payloads enter
        via :meth:`submit` and leave through their :class:`Ticket` (and
        optional callback) in *completion* order — the reorder buffer of
        :meth:`run` is the caller's concern here (an online server wants
        each result the moment it exists, not after its predecessors)."""
        if self._used:
            raise RuntimeError(
                f"{self.name}: executor already used (run() and start() "
                "are mutually exclusive, one lifecycle per executor)")
        self._used = True
        self._service = True
        self._qs = [queue.Queue(maxsize=s.depth) for s in self.stages]
        self._out_q: "queue.Queue" = queue.Queue(
            maxsize=self.stages[-1].depth)
        for i, st in enumerate(self.stages):
            self._lane_counts[st.name] = st.lanes
            for lane in range(st.lanes):
                self._spawn_service_worker(i, lane)
        disp = threading.Thread(target=self._dispatch_loop, daemon=True,
                                name=f"{self.name}/dispatch")
        disp.start()
        self._service_threads.append(disp)
        return self

    def _spawn_service_worker(self, idx: int, lane: int):
        t = threading.Thread(
            target=self._service_worker, args=(idx,), daemon=True,
            name=f"{self.name}/{self.stages[idx].name}.{lane}")
        t.start()
        self._service_threads.append(t)

    def _service_worker(self, idx: int):
        stage = self.stages[idx]
        in_q = self._qs[idx]
        nxt = self._qs[idx + 1] if idx + 1 < len(self._qs) else self._out_q
        while True:
            got = self._get(in_q)
            if got is _DONE:          # cancelled
                return
            if isinstance(got, _Retire):   # live lane removal
                return
            seq, item, payload = got
            if not isinstance(payload, _Failure):
                payload = self._call(stage, payload, item)
            self._put(nxt, (seq, item, payload))

    def _deliver_rejection(self, ticket: Ticket, callback):
        """Reject a ticket AND fire its callback: completion callbacks
        are the only notification some callers have (the server's
        result scatter), so a close()-time rejection that skipped them
        would leave those callers blocked forever."""
        ticket._reject(RuntimeError(f"{self.name}: executor closed"))
        if callback is not None:
            try:
                callback(ticket)
            except BaseException:
                pass

    def _dispatch_loop(self):
        """Sink for service mode: resolve tickets in completion order."""
        while True:
            got = self._get(self._out_q)
            if got is _DONE:          # cancelled
                return
            seq, _item, payload = got
            with self._lock:
                entry = self._tickets.pop(seq, None)
                if not self._tickets:
                    self._idle.notify_all()
            if entry is None:         # closed under us; ticket rejected
                continue
            ticket, callback = entry
            if isinstance(payload, _Failure):
                ticket._reject(payload.err)
            else:
                ticket._resolve(payload)
            if callback is not None:
                try:
                    callback(ticket)
                except BaseException:
                    pass              # callbacks must not kill the sink

    def submit(self, payload, *,
               callback: Optional[Callable[[Ticket], None]] = None,
               item: Optional[int] = None) -> Ticket:
        """Enqueue one payload; returns its :class:`Ticket`.

        Blocks while the first stage queue is full — the executor's
        bounded queues are the backpressure surface (admission control
        with a hard depth bound lives in the caller, e.g. the
        micro-batcher).  ``callback(ticket)`` fires on the dispatcher
        thread the moment the payload completes (out of order).
        ``item`` is the id the payload's stage spans carry (default:
        the ticket's sequence number)."""
        if not self._service:
            raise RuntimeError(f"{self.name}: submit() requires service "
                               "mode — call start() first")
        with self._lock:
            if self._closed:
                raise RuntimeError(f"{self.name}: executor closed")
            seq = self._submit_seq
            self._submit_seq += 1
            ticket = Ticket(seq)
            self._tickets[seq] = (ticket, callback)
        if not self._put(self._qs[0],
                         (seq, seq if item is None else item, payload)):
            with self._lock:
                entry = self._tickets.pop(seq, None)
                if not self._tickets:
                    self._idle.notify_all()
            if entry is not None:    # close() didn't already reject it
                self._deliver_rejection(ticket, callback)
            return ticket
        return ticket

    def pending(self) -> int:
        """Number of submitted-but-unresolved payloads."""
        with self._lock:
            return len(self._tickets)

    def drain(self, timeout: Optional[float] = None) -> bool:
        """Block until every submitted payload has been delivered (or
        ``timeout`` elapses).  Returns True when idle."""
        with self._idle:
            return self._idle.wait_for(
                lambda: not self._tickets or self._closed, timeout)

    def reconfigure(self, lanes: Dict[str, int]) -> Dict[str, int]:
        """Re-apply a lane allocation to a *running* service executor.

        Growing a stage spawns workers immediately; shrinking enqueues
        retire tokens that the next free worker of that stage consumes —
        queued payloads are never dropped, and results stay bit-identical
        because stage fns are pure.  Returns the new lane map."""
        if not self._service:
            raise RuntimeError(f"{self.name}: reconfigure() requires "
                               "service mode")
        retire: List[int] = []     # stage indices, one entry per token
        with self._lock:
            if self._closed:
                raise RuntimeError(f"{self.name}: executor closed")
            for i, st in enumerate(self.stages):
                target = lanes.get(st.name)
                if target is None:
                    continue
                target = max(1, int(target))
                cur = self._lane_counts[st.name]
                if target > cur:
                    for lane in range(cur, target):
                        self._spawn_service_worker(i, lane)
                elif target < cur:
                    retire.extend([i] * (cur - target))
                self._lane_counts[st.name] = target
                st.lanes = target
            out = dict(self._lane_counts)
        # retire tokens ride the bounded stage queues; putting them
        # outside the lock keeps the dispatcher free to drain results
        # (the queues only empty while the sink keeps consuming)
        for i in retire:
            self._put(self._qs[i], _Retire())
        return out

    def lane_counts(self) -> Dict[str, int]:
        """Current {stage: lanes} (live, reflects reconfigure())."""
        if self._service:
            with self._lock:
                return dict(self._lane_counts)
        return {s.name: s.lanes for s in self.stages}

    # ------------------------------------------------------------------
    def run(self, items: Iterable) -> Iterator:
        """Pump ``items`` through the graph; yields results in order.

        Single-use: the sink cancels all workers when the stream ends,
        so a second ``run()`` needs a fresh executor."""
        if self._used:
            raise RuntimeError(
                f"{self.name}: LaneExecutor.run() is single-use — "
                "construct a new executor for another stream")
        self._used = True
        qs = [queue.Queue(maxsize=s.depth) for s in self.stages]
        # the sink queue is bounded too: a slow consumer must exert
        # backpressure on the whole graph, not buffer the entire stream
        out_q: "queue.Queue" = queue.Queue(maxsize=self.stages[-1].depth)

        def feeder():
            seq = 0
            try:
                for item in items:
                    if not self._put(qs[0], (seq, item)):
                        return
                    seq += 1
            except BaseException as e:  # source iterator failed: the
                # error takes the next sequence slot so every item fed
                # before it still comes out first
                self._put(qs[0], (seq, _Failure(e)))
            finally:
                self._put(qs[0], _DONE)

        def worker(idx: int, stage: Stage, done_box: dict):
            in_q = qs[idx]
            nxt = qs[idx + 1] if idx + 1 < len(qs) else out_q
            while True:
                got = self._get(in_q)
                if got is _DONE:
                    with done_box["lock"]:
                        done_box["n"] += 1
                        last = done_box["n"] >= stage.lanes
                    # siblings each need to see the sentinel once; the
                    # last lane forwards it downstream instead
                    self._put(nxt if last else in_q, _DONE)
                    return
                seq, payload = got
                if not isinstance(payload, _Failure):
                    payload = self._call(stage, payload, seq)
                self._put(nxt, (seq, payload))

        threads = [threading.Thread(target=feeder, daemon=True,
                                    name=f"{self.name}/feed")]
        for i, st in enumerate(self.stages):
            box = {"lock": threading.Lock(), "n": 0}
            for lane in range(st.lanes):
                threads.append(threading.Thread(
                    target=worker, args=(i, st, box), daemon=True,
                    name=f"{self.name}/{st.name}.{lane}"))
        for t in threads:
            t.start()

        # sink: reorder buffer keyed by sequence number.  The sentinel
        # protocol guarantees _DONE reaches out_q only after every
        # result (each lane finishes + forwards its in-flight item
        # before consuming the sentinel), so draining until _DONE then
        # flushing the buffer sees every sequence number exactly once.
        # Each result's hand-over, the consumer's work on it included,
        # is a "sink" span.
        buf: Dict[int, Any] = {}
        next_seq = 0
        done = False
        try:
            while not done or buf:
                if not done:
                    got = self._get(out_q)
                    if got is _DONE:
                        done = True
                        continue
                    seq, payload = got
                    buf[seq] = payload
                while next_seq in buf:
                    with spans.span("sink", item=next_seq):
                        payload = buf.pop(next_seq)
                        next_seq += 1
                        if isinstance(payload, _Failure):
                            raise payload.err
                        yield payload
                if done and buf and next_seq not in buf:
                    raise RuntimeError(
                        f"{self.name}: lost sequence {next_seq} "
                        f"(have {sorted(buf)})")
        finally:
            self.close()

    def map(self, items: Iterable) -> List:
        """Eager form of :meth:`run`."""
        return list(self.run(items))


def lanes_from_allocation(stage_names: Sequence[str],
                          streams: Sequence[int]) -> Dict[str, int]:
    """{stage: lanes} from an ``allocator.Allocation.streams`` vector."""
    return {n: max(1, int(s)) for n, s in zip(stage_names, streams)}

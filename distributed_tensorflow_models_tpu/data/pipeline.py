"""Host prefetch pipeline: the QueueRunner/Coordinator replacement.

The reference overlaps input with compute via graph-resident queues driven
by *many* Python ``QueueRunner`` threads per queue under a ``Coordinator``
(SURVEY.md §2.2 F10/F11; TF queue_runner_impl.py:34, coordinator.py:28).
The TPU-native split: :class:`HostPipeline` produces numpy batches into a
bounded buffer — one background producer thread by default, or an
N-worker pool (``num_workers > 1``) that restores the reference's
producer parallelism for decode/augment-bound inputs — and
:class:`DevicePrefetcher` keeps a couple of batches resident on the mesh
so the next step's transfer overlaps the current step's compute.

The worker pool keeps the Coordinator semantics AND, unlike the
reference's free-running queue runners, stays deterministic: a serial
dispatcher advances the dataset's cheap cursor (``next_work()``,
datasets.py) and enqueues indexed work items; workers execute the pure
``assemble(work)`` in parallel; an ordered-reassembly stage releases
batches strictly in dispatch-index order.  The emitted stream is
therefore bit-identical for any worker count, a producer error surfaces
at exactly the position it occurred (after every earlier good batch has
drained), and the resume contract below is unchanged.

Unlike the reference's queues, the pipeline is *checkpointable*: each batch
carries the producer state that follows it, so `state` after consuming
batch k resumes at batch k+1 exactly (SURVEY.md §5.4 gap).

Batch arrays are recycled, not reallocated (an ImageNet batch is 154 MB,
and touching a fresh one costs twenty times the copy into one that
already exists): a dataset with ``recycle`` (``ArrayDataset``) writes
the next batch into arrays its consumer gave back through
:meth:`HostPipeline.release`.  The invariant: **a buffer is rewritten
only after (a) its consumer released it and (b) no device array can
still read it.**  :class:`DevicePrefetcher` is the one caller in the
program and observes (b) itself: it releases a host batch only once
every array placed from it ``is_ready()`` (the transfer out of the numpy
memory is asynchronous on an accelerator), and never on a mesh of CPU
devices, whose arrays may alias the numpy memory for their whole life.
Whoever iterates a ``HostPipeline`` and never calls ``release`` gets
fresh arrays for ever, none rewritten under it.  Nothing waits for a
buffer: no free one means allocate.

Telemetry: all stages record into an injectable
:class:`...telemetry.MetricsRegistry` (default: the process-global one) —
``pipeline/host_queue_depth`` + ``pipeline/producer_wait`` from the host
producer, ``pipeline/worker_busy/<i>`` per-worker utilization +
``pipeline/reassembly_wait`` from the pool, ``pipeline/prefetch_fill``
from the device stage.  Those are the stages'
*waits*; their two pieces of *work* are timed once per batch:
``pipeline/assemble`` (the dataset producing a batch, in the serial
producer or in whichever pool worker ran it) and ``pipeline/shard`` (the
host-to-device placement, with ``pipeline/bytes``);
``pipeline/buffer_reused`` and ``pipeline/buffer_fresh`` count the
batches assembled into recycled and into new arrays.  High producer wait =
consumer-bound (healthy); high prefetch-fill p95 = the host stream is the
bottleneck — then worker_busy vs reassembly_wait splits "pool too small /
decode-bound" from "serial cursor-bound" (README "Performance").
"""

from __future__ import annotations

import logging
import queue
import threading
import time
from typing import Any, Iterator, Optional

from distributed_tensorflow_models_tpu import telemetry

PyTree = Any

log = logging.getLogger("dtm")

# Every stage times its waits and its work with
# ``MetricsRegistry.record_since``: always into the timer, into the
# tracer's ring only from a millisecond up.  The ring exists to hold
# *stalls* for the flight recorder / fleet timeline, and a healthy
# pipeline's thousands of sub-millisecond records would evict exactly
# the events a post-mortem needs.


def _batch_bytes(batch: PyTree) -> int:
    import jax

    return sum(
        getattr(x, "nbytes", 0) for x in jax.tree_util.tree_leaves(batch)
    )


def _all_ready(placed: PyTree) -> bool:
    """Whether every device array of a placed batch is there, i.e. no
    transfer can still be reading the host arrays it came from.  (Of an
    array its consumer donated nobody can tell any more: not ready.)"""
    import jax

    return all(
        not x.is_deleted() and x.is_ready()
        for x in jax.tree_util.tree_leaves(placed)
    )


class _Stop:
    pass


_STOP = _Stop()


class _Failure:
    """A producer-side error travelling the queues as a payload, so the
    ordered-release stage surfaces it at the position it occurred."""

    __slots__ = ("error",)

    def __init__(self, error: BaseException):
        self.error = error


class HostPipeline:
    """Batch producer with bounded buffering: one background thread, or an
    ordered worker pool.

    ``dataset`` must be iterable (yielding numpy pytrees) and may expose
    ``get_state()/set_state()`` for resume.  With ``num_workers > 1`` it
    must additionally expose the worker-pool split (``next_work()`` +
    pure ``assemble(work)`` — every dataset in ``datasets.py`` does);
    datasets without it fall back to the serial producer with a warning.

    Pool topology (all threads daemon, all loops cooperative on the stop
    event): ``host-pipeline`` (dispatcher) advances the cursor serially
    and enqueues ``(index, work, state-after)``; ``data-worker-<i>``
    threads run ``assemble`` in parallel; ``host-pipeline-reassembly``
    releases results strictly in index order into the bounded consumer
    buffer.  Because release is ordered and state was captured at
    dispatch, the checkpointable state follows the last *released* batch
    exactly as in the serial path.  In-flight work (dispatched, not yet
    handed to the consumer buffer) is bounded by the consumer: the
    dispatcher takes one of ``num_workers + prefetch`` permits per item
    and reassembly gives it back on delivery, so a slow consumer stops
    the workers instead of letting them fill the results queue.
    """

    def __init__(
        self,
        dataset,
        *,
        prefetch: int = 4,
        num_workers: int = 1,
        registry: Optional[telemetry.MetricsRegistry] = None,
    ):
        self._dataset = dataset
        self._registry = (
            registry if registry is not None else telemetry.get_registry()
        )
        self._buffer: queue.Queue = queue.Queue(maxsize=prefetch)
        self._error: Optional[BaseException] = None
        self._error_raised = False
        self._stop_event = threading.Event()
        self._state: Optional[dict] = (
            dataset.get_state() if hasattr(dataset, "get_state") else None
        )
        # Pool wind-down, distinct from the consumer-facing stop event:
        # set by reassembly when it exits early (producer error) so the
        # dispatcher and workers stop feeding the unbounded results queue
        # while the consumer is still draining buffered good batches —
        # the STOP sentinel (gated on _stop_event only) still goes out.
        self._pool_stop = threading.Event()
        # Both present from the start, so that telemetry.json carries the
        # pair (and their share) whether or not reuse ever engages.
        self._registry.counter(telemetry.BUFFER_REUSED)
        self._registry.counter(telemetry.BUFFER_FRESH)
        pooled = num_workers > 1
        if pooled and not (
            hasattr(dataset, "next_work") and hasattr(dataset, "assemble")
        ):
            log.warning(
                "num_workers=%d requested but %s does not expose the "
                "next_work/assemble worker-pool split; using the serial "
                "producer",
                num_workers,
                type(dataset).__name__,
            )
            pooled = False
        self._num_workers = num_workers if pooled else 1
        # Free buffers worth keeping per leaf signature: what a fed
        # pipeline holds at once (the consumer buffer, one per worker,
        # the batch in the consumer's hands), plus what the releasing
        # stage says it holds (release()'s ``downstream``).
        self._retain = prefetch + self._num_workers + 1
        if pooled:
            # In-flight permits = pool width + prefetch: enough work to
            # keep every worker fed while the consumer drains, small
            # enough that dispatch (and so checkpoint state, and the
            # memory of assembled batches) never runs far ahead of
            # release.  A permit is put per dispatched item (blocking
            # when all are out) and taken back when reassembly hands the
            # batch to the consumer buffer.
            self._inflight: queue.Queue = queue.Queue(
                maxsize=num_workers + prefetch
            )
            self._work_q: queue.Queue = queue.Queue(
                maxsize=num_workers + prefetch
            )
            # Unbounded on purpose: the permits bound what can be in
            # it, and a bounded results queue could deadlock reassembly
            # waiting for an index a blocked worker holds.
            self._results_q: queue.Queue = queue.Queue()
            self._dispatched = 0
            self._dispatch_done = False
            # Reassembly's hold-back set, an attribute so stop() can
            # sweep it (with the results queue) for a failure that never
            # reached the release point.
            self._pending: dict[int, tuple] = {}
            self._threads = [
                threading.Thread(
                    target=self._dispatch, name="host-pipeline", daemon=True
                ),
                *(
                    threading.Thread(
                        target=self._worker,
                        args=(i,),
                        name=f"data-worker-{i}",
                        daemon=True,
                    )
                    for i in range(num_workers)
                ),
                threading.Thread(
                    target=self._reassemble,
                    name="host-pipeline-reassembly",
                    daemon=True,
                ),
            ]
        else:
            self._threads = [
                threading.Thread(
                    target=self._run, name="host-pipeline", daemon=True
                )
            ]
        for t in self._threads:
            t.start()

    # -- queue helpers (every blocking op must observe the stop event) ----

    def _put_stop_aware(self, q: queue.Queue, item) -> bool:
        """Put, polling the stop event; False if stop was requested."""
        while not self._stop_event.is_set():
            try:
                q.put(item, timeout=0.1)
                return True
            except queue.Full:
                continue
        return False

    def _pool_halted(self) -> bool:
        return self._stop_event.is_set() or self._pool_stop.is_set()

    def _put_pool_aware(self, q: queue.Queue, item) -> bool:
        """Put, polling stop AND pool wind-down; False if either fired."""
        while not self._pool_halted():
            try:
                q.put(item, timeout=0.1)
                return True
            except queue.Full:
                continue
        return False

    def _count_buffers(self) -> None:
        """One count per assembled batch, on the thread that assembled
        it: into recycled arrays, or into new ones (any dataset that
        cannot say allocates)."""
        reused = getattr(self._dataset, "last_assemble_reused", None)
        self._registry.counter(
            telemetry.BUFFER_REUSED
            if reused is not None and reused()
            else telemetry.BUFFER_FRESH
        ).inc()

    # -- serial producer (num_workers == 1 or no pool protocol) -----------

    def _run(self) -> None:
        reg = self._registry
        try:
            batches = iter(self._dataset)
            while True:
                # The dataset's own work for one batch (gather, decode,
                # augment): what a pool worker's ``assemble`` does.
                t0 = time.perf_counter()
                try:
                    batch = next(batches)
                except StopIteration:
                    break
                reg.record_since(telemetry.ASSEMBLE, t0)
                self._count_buffers()
                state = (
                    self._dataset.get_state()
                    if hasattr(self._dataset, "get_state")
                    else None
                )
                # Time blocked on a full buffer: high producer wait means
                # the consumer is the bottleneck — the healthy state.
                t0 = time.perf_counter()
                delivered = self._put_stop_aware(
                    self._buffer, (batch, state)
                )
                reg.record_since(telemetry.PRODUCER_WAIT, t0)
                reg.gauge(telemetry.HOST_QUEUE_DEPTH).set(
                    self._buffer.qsize()
                )
                if not delivered:
                    return
        except BaseException as e:  # propagate like Coordinator.join
            self._error = e
        finally:
            # The STOP sentinel must not be dropped: without it a consumer
            # blocks forever after draining the buffer (and a stored error
            # would never surface).  Retry until delivered or stop requested.
            self._put_stop_aware(self._buffer, (_STOP, None))

    # -- worker pool -------------------------------------------------------

    def _dispatch(self) -> None:
        """Serial cursor walk: the only thread that touches the dataset's
        mutable state.  State is captured immediately after ``next_work``
        so it names the position *after* the dispatched batch — the
        resume-exact value released alongside that batch downstream."""
        idx = 0
        try:
            while not self._pool_halted():
                # A permit per item in flight: the consumer's pace, not
                # the workers', decides how far the cursor runs ahead.
                if not self._put_pool_aware(self._inflight, None):
                    return
                try:
                    work = self._dataset.next_work()
                except StopIteration:
                    break
                state = (
                    self._dataset.get_state()
                    if hasattr(self._dataset, "get_state")
                    else None
                )
                if not self._put_pool_aware(
                    self._work_q, (idx, work, state)
                ):
                    return
                idx += 1
        except BaseException as e:
            # A cursor error holds position idx: reassembly releases
            # 0..idx-1 first, then surfaces it — straight to results, no
            # worker involved.
            self._results_q.put((idx, _Failure(e), None))
            idx += 1
        finally:
            self._dispatched = idx
            self._dispatch_done = True
            for _ in range(self._num_workers):
                if not self._put_pool_aware(self._work_q, _STOP):
                    break

    def _worker(self, wid: int) -> None:
        reg = self._registry
        busy_gauge = reg.gauge(f"{telemetry.WORKER_BUSY}/{wid}")
        t_start = time.perf_counter()
        busy = 0.0
        while not self._pool_halted():
            try:
                item = self._work_q.get(timeout=0.1)
            except queue.Empty:
                continue
            if isinstance(item, _Stop):
                return
            idx, work, state = item
            t0 = time.perf_counter()
            try:
                payload = self._dataset.assemble(work)
                reg.record_since(telemetry.ASSEMBLE, t0)
                self._count_buffers()
            except BaseException as e:
                payload = _Failure(e)
            now = time.perf_counter()
            busy += now - t0
            busy_gauge.set(busy / max(now - t_start, 1e-9))
            self._results_q.put((idx, payload, state))

    def _reassemble(self) -> None:
        """Ordered release: batches leave in dispatch-index order no
        matter which worker finished first, so the stream (and the state
        riding with each batch) is identical to the serial producer's."""
        reg = self._registry
        pending = self._pending
        next_idx = 0
        try:
            while not self._stop_event.is_set():
                # Wait for the *next in-order* index.  This timer is the
                # pool's stall signal: fat p95 with workers near 1.0 busy
                # = pool too small (decode-bound); fat p95 with workers
                # idle = the serial cursor is the bottleneck.
                t0 = time.perf_counter()
                while next_idx not in pending:
                    if self._stop_event.is_set():
                        return
                    if (
                        self._dispatch_done
                        and next_idx >= self._dispatched
                    ):
                        return
                    try:
                        idx, payload, state = self._results_q.get(
                            timeout=0.1
                        )
                    except queue.Empty:
                        continue
                    pending[idx] = (payload, state)
                reg.record_since(telemetry.REASSEMBLY_WAIT, t0)
                payload, state = pending.pop(next_idx)
                next_idx += 1
                if isinstance(payload, _Failure):
                    # Surfaces after every earlier good batch has drained
                    # — the position-exact Coordinator contract.
                    self._error = payload.error
                    return
                # Blocked on a full buffer = consumer-bound (healthy) —
                # the same signal the serial producer records.
                t0 = time.perf_counter()
                delivered = self._put_stop_aware(
                    self._buffer, (payload, state)
                )
                reg.record_since(telemetry.PRODUCER_WAIT, t0)
                reg.gauge(telemetry.HOST_QUEUE_DEPTH).set(
                    self._buffer.qsize()
                )
                if not delivered:
                    return
                self._inflight.get_nowait()
        finally:
            # Wind the pool down on EVERY exit — on the error path the
            # dispatcher and workers would otherwise free-run an
            # infinite dataset into the unbounded results queue while
            # the consumer drains buffered batches toward the error.
            self._pool_stop.set()
            self._put_stop_aware(self._buffer, (_STOP, None))

    # -- consumer side -----------------------------------------------------

    def __iter__(self) -> Iterator[PyTree]:
        return self

    def __next__(self) -> PyTree:
        # Buffered good batches drain before a producer error surfaces —
        # the error is raised at the position it occurred, not earlier.
        item, state = self._buffer.get()
        # Sample depth on the consumer side too: a drained queue must
        # read 0, not the last depth the producer happened to publish.
        self._registry.gauge(telemetry.HOST_QUEUE_DEPTH).set(
            self._buffer.qsize()
        )
        if isinstance(item, _Stop):
            if self._error is not None:
                self._error_raised = True
                raise self._error
            raise StopIteration
        self._state = state
        return item

    def get_state(self) -> Optional[dict]:
        """Producer state as of the last *consumed* batch (resume-exact)."""
        return self._state

    @property
    def prefetch(self) -> int:
        """How many finished batches the consumer buffer holds."""
        return self._buffer.maxsize

    def release(self, batch: PyTree, *, downstream: int = 0) -> None:
        """The consumer has finished with ``batch`` (one this pipeline
        emitted): its arrays may be overwritten by a later batch.

        Call it only when nothing can read the arrays any more,
        including a device array placed from them (see the module
        docstring; ``DevicePrefetcher`` does it for ``fit``).  Never
        calling it is always safe.  ``downstream`` is how many more
        batches the calling stage holds, which are so many more buffers
        worth keeping.  A dataset that does not recycle ignores the
        call, and so does one handed arrays it did not make."""
        recycle = getattr(self._dataset, "recycle", None)
        if recycle is not None:
            recycle(batch, self._retain + downstream)

    def stop(self, raise_pending: bool = True) -> None:
        """Cooperative stop — ``Coordinator.request_stop`` + ``join``
        (TF coordinator.py:181,318).  Like ``Coordinator.join``, a stored
        producer error that never reached the consumer is re-raised here
        (after the threads are down) rather than silently dropped, and a
        thread that outlives the join timeout is reported.

        ``raise_pending=False`` downgrades that re-raise to a warning —
        for callers tearing the pipeline down because they are about to
        *abandon this stream position anyway* (the divergence-rollback
        path rebuilds the pipeline at the restored cursor), where an
        in-flight producer error from the doomed lookahead must not mask
        the recovery in progress."""
        self._stop_event.set()
        while True:  # drain so the producer unblocks
            try:
                self._buffer.get_nowait()
            except queue.Empty:
                break
        for t in self._threads:
            t.join(timeout=5.0)
            if t.is_alive():
                log.warning(
                    "pipeline thread %s still alive after 5s join timeout",
                    t.name,
                )
        if self._error is None and hasattr(self, "_results_q"):
            # A pooled failure may still be in flight — produced by a
            # worker but not yet walked past by reassembly when stop cut
            # it short.  Sweep the results queue and the hold-back set
            # (threads are joined; no writers remain) and surface the
            # earliest-index failure, matching the serial path where the
            # error is stored the moment it is raised.
            while True:
                try:
                    idx, payload, state = self._results_q.get_nowait()
                except queue.Empty:
                    break
                self._pending[idx] = (payload, state)
            failures = [
                (idx, payload)
                for idx, (payload, _) in self._pending.items()
                if isinstance(payload, _Failure)
            ]
            if failures:
                self._error = min(failures, key=lambda f: f[0])[1].error
        if self._error is not None and not self._error_raised:
            self._error_raised = True
            if not raise_pending:
                log.warning(
                    "host pipeline stopped with pending producer error "
                    "(suppressed by caller): %r",
                    self._error,
                )
                return
            log.error(
                "host pipeline stopped with pending producer error: %r",
                self._error,
            )
            raise self._error


class DevicePrefetcher:
    """Keep ``depth`` sharded batches ahead on the mesh.

    Transfers the *next* batch to device while the current step computes —
    the role of the reference's in-graph staging between queue and compute.

    Each buffered batch carries the producer state captured when it was
    pulled, and :meth:`get_state` returns the state of the last batch
    *handed to the consumer* — so a checkpoint taken mid-training resumes
    at exactly the next unconsumed batch, never skipping the ``depth``
    batches sitting in this buffer.

    It also keeps each host batch beside the placed one and gives it back
    to the upstream (``release``, where the upstream has it) once the
    placed batch has left for the loop and the transfer out of the host
    arrays is over, which it looks at on every pull and never waits for.
    On a mesh of CPU devices nothing is released: a CPU device array may
    alias the numpy memory it was placed from for as long as it lives.
    """

    def __init__(self, iterator, mesh, *, depth: int = 2,
                 seq_dim: Optional[int] = None,
                 registry: Optional[telemetry.MetricsRegistry] = None):
        import functools

        from distributed_tensorflow_models_tpu.core import sharding

        self._it = iter(iterator)
        self._source = iterator
        self._mesh = mesh
        self._registry = (
            registry if registry is not None else telemetry.get_registry()
        )
        self._shard = functools.partial(
            sharding.shard_batch, seq_dim=seq_dim
        )
        # (placed batch, producer state, host batch to release or None)
        self._buf: list[tuple[PyTree, Optional[dict], Optional[PyTree]]] = []
        self._depth = depth
        self._release = (
            None
            if mesh.devices.flat[0].platform == "cpu"
            else getattr(iterator, "release", None)
        )
        # (placed batch, host batch) handed to the loop whose transfer
        # was still running then, oldest first.  The loop can pull faster
        # than transfers end only for as long as batches were ready for
        # it, here and in the upstream's buffer: so many may wait.
        self._unreleased: list[tuple[PyTree, PyTree]] = []
        self._max_unreleased = depth + getattr(iterator, "prefetch", 0)
        self._state: Optional[dict] = (
            iterator.get_state() if hasattr(iterator, "get_state") else None
        )
        # An upstream error caught while *refilling* is deferred until the
        # buffered good batches have drained, then raised at the pull that
        # actually needs the failed position.  Raising it from the refill
        # inside __next__ would lose the batch just popped (and advance
        # ``_state`` past it) — a crash-time checkpoint would then resume
        # one batch ahead of what was trained, silently skipping data.
        self._pending_error: Optional[BaseException] = None
        self._exhausted = False
        self._fill()

    def _fill(self) -> None:
        reg = self._registry
        if self._pending_error is not None or self._exhausted:
            # The upstream already ended (error or clean stop); pulling
            # again would block on the host pipeline's drained buffer.
            return
        while len(self._buf) < self._depth:
            # Fill stall: time blocked on the upstream (host) stream.  A
            # fat p95 here is the data-stall smoking gun — the host
            # pipeline cannot keep the prefetch buffer full.
            t0 = time.perf_counter()
            try:
                batch = next(self._it)
            except StopIteration:
                self._exhausted = True
                return
            except (KeyboardInterrupt, SystemExit):
                # Hard aborts (second ctrl-C, watchdog escalation) must
                # act NOW — deferring one would train through buffered
                # batches first, or drop it entirely if the run ends.
                raise
            except BaseException as e:  # surfaces after the buffer drains
                # Loud at deferral time: if the run ends (train_steps
                # reached) before draining to the failed position, this
                # line is the error's only trace — the host pipeline
                # already counts it raised, so stop() won't re-raise.
                log.error(
                    "upstream pipeline error deferred until buffered "
                    "batches drain: %r", e,
                )
                self._pending_error = e
                return
            reg.record_since(telemetry.PREFETCH_FILL, t0)
            state = (
                self._source.get_state()
                if hasattr(self._source, "get_state")
                else None
            )
            # The transfer: host batch to sharded device arrays.
            nbytes = _batch_bytes(batch)
            t0 = time.perf_counter()
            placed = self._shard(self._mesh, batch)
            reg.record_since(telemetry.SHARD, t0, {"bytes": nbytes})
            reg.counter(telemetry.PIPELINE_BYTES).inc(nbytes)
            self._buf.append(
                (placed, state, batch if self._release is not None else None)
            )

    def __iter__(self) -> Iterator[PyTree]:
        return self

    def __next__(self) -> PyTree:
        if not self._buf:
            if self._pending_error is not None:
                error, self._pending_error = self._pending_error, None
                raise error
            raise StopIteration
        out, state, host = self._buf.pop(0)
        self._state = state
        if host is not None:
            self._unreleased.append((out, host))
            self._release_ready()
        self._fill()
        return out

    def _release_ready(self) -> None:
        """Give back, oldest first, the host batches whose transfers are
        over.  One whose transfer is not waits for a later pull (on the
        chip a 154 MB batch is there 36-53 ms after the call, three
        pulls of a loop that runs ahead of the device) and is dropped
        unreleased once too many wait: nothing here ever blocks."""
        waiting = self._unreleased
        while waiting and _all_ready(waiting[0][0]):
            self._release(waiting.pop(0)[1], downstream=self._depth)
        del waiting[: max(0, len(waiting) - self._max_unreleased)]

    def get_state(self) -> Optional[dict]:
        """Producer state as of the last batch the consumer received."""
        return self._state


class BatchStacker:
    """Assemble K consecutive batches into one stacked chunk for the fused
    multi-step train program (``core/train_loop.py::make_multi_step``).

    Sits after :class:`DevicePrefetcher` (sharded device batches in, one
    stacked chunk out): :meth:`next_chunk` pulls up to ``k`` batches and
    stacks every leaf on a new leading axis laid out ``P(None, <original
    spec>)`` — replicated across the chunk axis, unchanged within a row —
    which is exactly the layout ``lax.scan`` slices back into per-step
    batches with zero resharding.  A non-sharded (host numpy) upstream
    stacks plainly, so the stage is also usable host-side.

    Checkpointing: :meth:`get_state` returns the producer state of the
    *last* batch of the last chunk handed out, so a checkpoint taken at a
    chunk boundary resumes at exactly the next unconsumed batch — the
    same resume-exact contract as the per-batch stages above.

    Ragged tail: when the upstream ends mid-chunk, the partial chunk
    (length < k) is returned rather than dropped; the following call
    raises ``StopIteration``.
    """

    def __init__(self, iterator):
        self._it = iter(iterator)
        self._source = iterator
        self._state: Optional[dict] = (
            iterator.get_state() if hasattr(iterator, "get_state") else None
        )
        self._exhausted = False
        # jitted stack fns keyed by (chunk len, leaf signature): the jit
        # wrapper carries explicit out_shardings, so it must be built once
        # per shape class, not once per call (a per-call lambda would
        # recompile every chunk).
        self._stack_cache: dict = {}

    def next_chunk(self, k: int):
        """Return ``(stacked_chunk, n)`` with ``n = min(k, batches left)``
        rows; raises ``StopIteration`` once the upstream is exhausted."""
        if self._exhausted:
            raise StopIteration
        rows = []
        for _ in range(max(1, int(k))):
            try:
                rows.append(next(self._it))
            except StopIteration:
                self._exhausted = True
                break
        if not rows:
            raise StopIteration
        if hasattr(self._source, "get_state"):
            self._state = self._source.get_state()
        return self._stack(rows), len(rows)

    def _stack(self, rows):
        import jax
        import jax.numpy as jnp

        leaves, treedef = jax.tree_util.tree_flatten(rows[0])
        sig = (
            len(rows),
            treedef,
            tuple((leaf.shape, str(leaf.dtype)) for leaf in leaves),
        )
        fn = self._stack_cache.get(sig)
        if fn is None:
            from jax.sharding import NamedSharding, PartitionSpec

            def target(leaf):
                sh = getattr(leaf, "sharding", None)
                if isinstance(sh, NamedSharding):
                    return NamedSharding(
                        sh.mesh, PartitionSpec(None, *tuple(sh.spec))
                    )
                return None

            shardings = [target(leaf) for leaf in leaves]

            def stack(*rs):
                return jax.tree.map(lambda *xs: jnp.stack(xs), *rs)

            if all(s is not None for s in shardings):
                out_shardings = jax.tree_util.tree_unflatten(
                    treedef, shardings
                )
                fn = jax.jit(stack, out_shardings=out_shardings)
            else:
                # Host numpy / single-device upstream: plain stack.
                fn = stack
            self._stack_cache[sig] = fn
        return fn(*rows)

    def get_state(self) -> Optional[dict]:
        """Producer state as of the last batch in the last chunk."""
        return self._state

"""Bounded-prefetch streaming pipeline: overlap host production with
device dispatch.

Every default route used to run its two halves strictly back-to-back —
read+tokenize chunk i, THEN feed chunk i to the engine — so the obs traces
showed the host map phase and the device reduce dispatch serialized even
though nothing forces them to be (the round-5 bench: every 256MB text
workload at or barely above the 5x bar for exactly this reason).  XLA's
async dispatch already hides the *device* side of a feed; what it cannot
hide is the *host* side of producing the next chunk.  This module hides it:

    producer thread:  read + tokenize chunk i+1 .. i+depth   (C++ scan or
                      CPython builtins — both release or don't hold the GIL
                      for the hot part)
    consumer thread:  pad + device_put + merge-dispatch chunk i

:class:`ChunkPrefetcher` wraps ANY iterator with a depth-``N`` bounded
queue (the backpressure bound: at most ``depth`` chunks of host memory in
flight) and measures the overlap it achieved:

* ``produce_s`` — host time spent producing items (the work to hide);
* ``wait_s``    — consumer time spent stalled for the next item (the part
  of ``produce_s`` that was NOT hidden);
* ``overlap_ratio`` — ``1 - wait_s / produce_s``: 1.0 means every host
  second ran behind device dispatch, 0.0 means the pipeline degenerated
  to the serial schedule.

Ordering is the queue's FIFO, i.e. identical to the serial iteration, so
outputs — including checkpoint spill order and kill-resume replay — are
byte-identical to ``depth=1`` (pinned by tests/test_pipeline.py).
Exceptions (BaseException included: the kill-resume contract is a
``KeyboardInterrupt`` mid-map) propagate to the consumer after the items
produced before them, exactly like serial iteration.

Both halves are spans of the job's tracer, ``<name>/produce`` on the
producer thread and ``<name>/feed_wait`` on the consumer's, and their
sink keeps the ``pipeline/produce_ms`` / ``pipeline/feed_wait_ms``
counters live per chunk.  ``pipelined()`` is the driver-facing wrapper:
depth <= 1 returns the iterator untouched (the serial baseline path,
zero new machinery), and with an :class:`~map_oxidize_tpu.obs.Obs`
bundle it keeps the ``pipeline/overlap_ratio`` gauge.

:class:`BlockStager` is the prefetcher grown into a **batching,
double-buffered device stager** (the dispatch-floor attack's host half):
it groups the stream into ``batch``-chunk blocks and runs the caller's
``stage_fn`` — pinned-buffer assembly + the async ``device_put`` — in
the producer thread, so the transfer of block i+1 overlaps the device
compute of block i while the scan-batched step retires B chunks per
launch.
"""

from __future__ import annotations

import queue
import threading
from typing import Iterable, Iterator, TypeVar

from map_oxidize_tpu.obs.trace import Tracer
from map_oxidize_tpu.utils.logging import get_logger

_log = get_logger(__name__)

T = TypeVar("T")

_DONE = object()


class ChunkPrefetcher:
    """Depth-bounded background producer over any iterator.

    The producer thread starts lazily on first ``__iter__`` and dies with
    the stream: exhaustion, a producer error, or the consumer abandoning
    the iteration (generator close / driver abort) all stop it — the
    abandon path sets a stop flag and drains the queue so a producer
    blocked on ``put`` wakes and exits instead of pinning ``depth``
    chunks of host memory until process end.

    A source that runs its own producers ahead of its consumer
    (``runs_ahead``, the native scan's :class:`~map_oxidize_tpu.native.
    build.OrderedScan`) gets no thread: the consumer pulls it directly
    under ``<name>/feed_wait``, its producers time their own work,
    ``produce_s`` is theirs, and abandoning the iteration closes it.
    """

    def __init__(self, it: Iterable[T], depth: int, name: str = "pipeline",
                 obs=None):
        if depth < 1:
            raise ValueError(f"prefetch depth must be >= 1, got {depth}")
        from map_oxidize_tpu.obs.context import bind_current

        self._it = iter(it)
        self._inline = getattr(it, "runs_ahead", False)
        self._q: queue.Queue = queue.Queue(maxsize=depth)
        self._name = name
        self._stop = False
        self._err: BaseException | None = None
        self._obs = obs
        #: the job's tracer: its spans time both halves and feed the
        #: ``pipeline/*_ms`` counters live per chunk (the attribution
        #: ledger and the heartbeat's where= token read them mid-run).
        #: Without a job, a tracer that keeps nothing times them alone
        self._tracer = (obs.tracer if obs is not None
                        else Tracer(enabled=False, sink=_discard))
        # bind-on-spawn: the producer runs the job's host half (read +
        # tokenize/map), and anything it observes — a device-mapper
        # dispatch, a recompile warning — must route to the SPAWNING
        # job's ObsContext; a bare thread starts unbound and would fall
        # back to the ledger's last-activated job, which under a
        # resident server multiplexing jobs is the wrong one
        self._thread = threading.Thread(
            target=bind_current(self._produce), daemon=True,
            name=f"{name}-prefetch")
        self.depth = depth
        #: host time spent producing items (read+tokenize/map)
        self.produce_s = 0.0
        #: consumer time spent stalled waiting for the next item
        self.wait_s = 0.0
        self.items = 0

    # --- producer ---------------------------------------------------------

    def _produce(self) -> None:
        seq = 0
        try:
            while not self._stop:
                # the producer half of the queue handoff: seq= pairs
                # this span with the consumer's same-seq feed_wait span,
                # the producer->consumer edge the critical-path DAG
                # (obs/critpath.py) follows when the consumer stalled on
                # this item.  Exhaustion uses the sentinel default so no
                # StopIteration crosses the span (an error-tagged span
                # in every healthy trace would read as a failure)
                with self._tracer.span(f"{self._name}/produce",
                                       seq=seq) as sp:
                    item = next(self._it, _DONE)
                    if item is _DONE:
                        sp.set(exhausted=True)
                if item is _DONE:
                    return
                seq += 1
                self.produce_s += sp.dur
                # timed put loop instead of a blocking put: an abandoned
                # consumer only drains once, so a producer stuck in a
                # plain put() could miss the wakeup and leak its chunk
                while not self._stop:
                    try:
                        self._q.put(item, timeout=0.1)
                        break
                    except queue.Full:
                        continue
        except BaseException as e:  # noqa: BLE001 — hand EVERYTHING to the
            # consumer: a KeyboardInterrupt raised by a mapper mid-chunk is
            # the kill-resume contract, not an exit signal for this thread
            self._err = e
        finally:
            while not self._stop:
                try:
                    self._q.put(_DONE, timeout=0.1)
                    break
                except queue.Full:
                    continue

    # --- consumer ---------------------------------------------------------

    @property
    def overlap_ratio(self) -> float:
        """Fraction of host produce time hidden behind consumer work."""
        if self.produce_s <= 0.0:
            return 1.0
        return max(0.0, 1.0 - self.wait_s / self.produce_s)

    def __iter__(self) -> Iterator[T]:
        if self._inline:
            def get():
                item = next(self._it, _DONE)
                self.produce_s = self._it.produce_s
                return item
        else:
            self._thread.start()
            get = self._q.get
        seq = 0
        try:
            while True:
                # the consumer half of the handoff: the span's wall IS
                # the stall waiting for item seq (zero when the producer
                # ran ahead) — same-seq as the producer's produce span
                with self._tracer.span(f"{self._name}/feed_wait",
                                       seq=seq) as sp:
                    item = get()
                seq += 1
                self.wait_s += sp.dur
                if item is _DONE:
                    if self._err is not None:
                        raise self._err
                    return
                self.items += 1
                if self._obs is not None:
                    self._obs.registry.count("pipeline/chunks")
                yield item
        finally:
            # abandon/exhaustion: release the producer if it is still
            # blocked, then let the daemon thread unwind
            self._stop = True
            if self._inline:
                self._it.close()
            try:
                while True:
                    self._q.get_nowait()
            except queue.Empty:
                pass


def _discard(name: str, dur_s: float, self_s: float) -> None:
    """Sink of a prefetcher with no job: its spans time, nothing keeps."""


def chunk_groups(items: Iterable, batch: int) -> list:
    """Group ``items`` into lists of at most ``batch`` (the last group
    may be short) — the block layout both :func:`staged_blocks` and
    :class:`BlockStager` consume."""
    if batch < 1:
        raise ValueError(f"dispatch batch must be >= 1, got {batch}")
    items = list(items)
    return [items[i:i + batch] for i in range(0, len(items), batch)]


def staged_blocks(groups: Iterable, stage_fn):
    """Serial staging generator (the ``depth<=1`` control arm of
    :class:`BlockStager`, and its producer body): yields
    ``stage_fn(group)`` for each pre-built group (see
    :func:`chunk_groups`)."""
    for group in groups:
        yield stage_fn(group)


class BlockStager(ChunkPrefetcher):
    """Batching, double-buffered device stager — the host half of the
    dispatch-floor attack.

    Runs ``stage_fn(group)`` — assembly of one pre-grouped block (see
    :func:`chunk_groups`) into a fresh staging buffer plus the async
    ``device_put`` — in the producer thread, so staging AND transferring
    block i+1 overlap the consumer's dispatch/compute of block i.  The
    caller builds the group sequence, which may span ITERATIONS of a
    multi-pass consumer (streamed k-means stages iteration i+1's first
    block while iteration i's tail block still computes — data blocks do
    not depend on the evolving carry, so the inter-iteration staging
    bubble is free to close).  ``stage_fn`` must hand its buffer's
    ownership to jax at the put (``utils.jax_compat.device_put_handoff``:
    the CPU backend zero-copy-aliases large host buffers and an
    accelerator's DMA read is async, so buffer REUSE corrupts in-flight
    blocks — measured, see tests/test_dispatch_batch.py).  Memory stays
    flat anyway: the depth-bounded queue backpressures the producer, so
    at most ``depth+1`` staged blocks exist host-side while HBM holds
    the executing block plus the prefetched ones — the double-buffer
    contract at the default ``depth=1``.

    ``produce_s`` here measures assembly+put per block — exactly the
    "host-produce" input the auto dispatch-batch roofline consumes.
    """

    def __init__(self, groups: Iterable, stage_fn,
                 depth: int = 1, name: str = "stager", obs=None):
        super().__init__(staged_blocks(groups, stage_fn),
                         depth, name=name, obs=obs)


def pipelined(it: Iterable[T], depth: int, obs=None,
              name: str = "pipeline",
              ratio_gauge: str | None = None) -> Iterable[T]:
    """Driver-facing wrapper: prefetch ``it`` with ``depth`` in-flight
    items, recording the overlap counters into ``obs`` when given.

    ``depth <= 1`` returns ``it`` unchanged — the serial baseline
    schedule, no thread, no counters — so ``--pipeline-depth 1`` is a
    true control arm, not a degenerate pipeline.

    ``ratio_gauge`` names an EXTRA gauge fed the same live overlap ratio
    — the push-shuffle drivers pass ``pipeline/shuffle_overlap_ratio``
    so the shuffle-behind-map overlap is separable from ordinary map
    prefetch in the ledger gate and the bench snapshots.
    """
    if depth <= 1:
        return it
    # the prefetcher's spans feed the pipeline/produce_ms and
    # pipeline/feed_wait_ms counters LIVE per chunk (the attribution
    # ledger's bucket feeds)
    pf = ChunkPrefetcher(it, depth - 1, name=name, obs=obs)

    def _set_ratio(reg) -> None:
        ratio = round(pf.overlap_ratio, 4)
        reg.set("pipeline/overlap_ratio", ratio)
        if ratio_gauge:
            reg.set(ratio_gauge, ratio)

    def _run():
        try:
            for item in pf:
                if obs is not None:
                    # live overlap gauge: the time-series recorder and
                    # /status read it MID-run; one locked gauge write
                    # per chunk is noise at chunk cadence
                    _set_ratio(obs.registry)
                yield item
        finally:
            if obs is not None and (pf.items or pf.produce_s):
                reg = obs.registry
                reg.set("pipeline/depth", depth)
                _set_ratio(reg)
                obs.tracer.instant(
                    f"{name}/pipeline_done", items=pf.items,
                    produce_ms=round(pf.produce_s * 1e3, 3),
                    wait_ms=round(pf.wait_s * 1e3, 3),
                    overlap_ratio=round(pf.overlap_ratio, 4))

    return _run()

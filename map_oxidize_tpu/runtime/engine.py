"""Streaming device reduce engines.

The TPU-side half of the pipeline.  Where the reference materializes every
map output to text files and re-parses them under one mutex
(``/root/reference/src/main.rs:103-109`` spill, 111-150 reduce), these
engines keep a device-resident accumulator of reduced ``(key, value)`` rows
and fold mapped batches into it as they stream in:

    host map -> pad to fixed batch -> device_put -> sort+segment combine
    (donated buffers, one cached XLA executable)

Batches are a fixed static shape so XLA compiles exactly one merge program;
short batches are padded with SENTINEL keys / identity values.  Dispatch is
async, so host tokenization of chunk N overlaps device reduction of chunk
N-1 — the double-buffering SURVEY.md §7 calls for, with no explicit machinery.

Two implementations share the host-side surface (``feed`` / ``finalize`` /
``top_k``), so the driver is engine-agnostic:

* :class:`DeviceReduceEngine` — one chip, one accumulator.
* :class:`map_oxidize_tpu.parallel.engine.ShardedReduceEngine` — a mesh of
  chips, per-shard accumulators, ``all_to_all`` key routing.

Engine contract for ``finalize()``: returns ``(hi, lo, vals, n_unique)``
device arrays where rows whose key is SENTINEL are padding and **may appear
anywhere** (the sharded layout interleaves each shard's padding tail);
consumers must mask on the sentinel, not slice ``[:n]``.

Overflow safety: every merge reports unique-key counts; engines poll them
periodically and raise rather than silently dropping keys.
"""

from __future__ import annotations

import abc
import time
from functools import partial

import numpy as np
import jax
import jax.numpy as jnp

from map_oxidize_tpu.api import MapOutput, Reducer
from map_oxidize_tpu.config import JobConfig
from map_oxidize_tpu.obs.compile import observed_jit
from map_oxidize_tpu.obs.trace import span_of
from map_oxidize_tpu.ops.hashing import SENTINEL
from map_oxidize_tpu.ops.segment_reduce import (
    _identity,
    make_accumulator,
    merge_into_accumulator,
    merge_packed_batch_into_accumulator,
    merge_packed_into_accumulator,
    pack_accumulator_state,
)
from map_oxidize_tpu.ops.topk import top_k_pairs_jit
from map_oxidize_tpu.utils.logging import get_logger

_log = get_logger(__name__)


class CapacityError(RuntimeError):
    """Keys were dropped: distinct keys exceeded the accumulator's maximum
    capacity; re-run with a larger ``key_capacity``."""


def pick_device(backend: str = "auto"):
    """Resolve the compute device: 'tpu' demands an accelerator, 'cpu' forces
    host, 'auto' takes jax's default ordering (accelerator first).

    The first ``jax.devices()`` of a process INITIALIZES the backend
    (hundreds of ms on CPU, seconds on a TPU) — wall the
    attribution ledger's ``setup`` bucket must see when it lands inside
    a phase, so the resolve is timed into ``attrib/init_ms`` on the
    recording job (subsequent calls cost ~0 and add noise-level
    counts)."""
    t0 = time.perf_counter()
    try:
        if backend == "auto":
            return jax.devices()[0]
        for d in jax.devices():
            if d.platform == backend:
                return d
        if backend == "cpu":  # cpu exists even when an accelerator leads
            return jax.devices("cpu")[0]
        raise RuntimeError(f"no {backend!r} device available; have "
                           f"{[d.platform for d in jax.devices()]}")
    finally:
        from map_oxidize_tpu.obs.context import current_obs

        obs = current_obs()
        # only when a phase is open: a pre-phase resolve (the fold
        # engines' construction path) is already inside the pre-phase
        # wall the ``attrib/setup_ms`` gauge stamps — counting it again
        # would double the setup bucket
        if obs is not None and getattr(obs, "current_phase", None):
            obs.registry.count("attrib/init_ms",
                               (time.perf_counter() - t0) * 1e3)


def next_pow2(n: int) -> int:
    return 1 << max(n - 1, 1).bit_length()


@partial(observed_jit, "engine/grow_concat")
@partial(jax.jit, donate_argnums=(0, 1, 2))
def _grow_concat(hi, lo, vals, p_hi, p_lo, p_vals):
    return (jnp.concatenate([hi, p_hi]), jnp.concatenate([lo, p_lo]),
            jnp.concatenate([vals, p_vals]))


class StreamingEngineBase(abc.ABC):
    """Shared host-side surface: batch padding, the feed loop, and the
    health-check cadence.  Subclasses own the device state and the merge
    executable.

    Batch sizing: rows are fed in slices of at most ``feed_batch``, each
    padded up to the next power of two (subclasses may round further via
    ``_round_batch``).  A handful of distinct shapes keeps XLA's executable
    cache small while short chunks avoid full-batch sort cost — a mapper
    emitting 30k combined rows must not pay for a 1M-row merge.

    Capacity growth: the accumulator starts at ``initial_key_capacity`` and
    grows by sentinel-pad steps (to the needed power of two, 2x minimum)
    toward ``key_capacity`` (the hard max).
    Growth happens *before* a merge could overflow, driven by a host-tracked
    upper bound on live keys (+= batch rows per merge, no device sync); the
    bound is refreshed from the device's exact count only when it would
    otherwise force a growth, so syncs stay rare and the feed path async.
    Past ``key_capacity``, merges drop keys — counted by a cumulative
    device-side counter that the health check turns into ``CapacityError``
    (an exactly-full accumulator is NOT an error; only actual drops are).
    """

    #: max rows per padded device batch; set by subclass __init__
    feed_batch: int
    #: current / maximum accumulator capacity (per shard, where sharded)
    capacity: int
    max_capacity: int

    def __init__(
        self,
        config: JobConfig,
        reducer: Reducer,
        value_shape: tuple = (),
        value_dtype=np.int32,
        overflow_check_every: int = 64,
    ):
        self.config = config
        self.combine = reducer.combine
        self.value_shape = tuple(value_shape)
        self.value_dtype = np.dtype(value_dtype)
        self._pad_val = np.asarray(_identity(self.combine, self.value_dtype))
        self._merges = 0
        self._check_every = overflow_check_every
        #: observability bundle (obs.Obs) injected by the driver; None
        #: keeps every record site a single attribute check
        self.obs = None
        self.rows_fed = 0
        self._stage: list = []   # host-side staging of mapped rows
        self._staged = 0
        self._n_unique = None    # device-side live-key count (per last merge)
        self._n_live_ub = 0      # host upper bound on live keys
        self._total_hint = None  # exact cap on distinct keys, if caller knows

    def _span(self, name: str, **attrs):
        """A span of the job's tracer; the null span with no ``Obs``."""
        return span_of(self.obs, name, **attrs)

    def _round_batch(self, n: int) -> int:
        """Padded size for an ``n``-row slice: next power of two, capped at
        ``feed_batch``.  Subclasses may round further (e.g. to a multiple of
        the shard count)."""
        return min(next_pow2(max(n, 512)), self.feed_batch)

    def _pad(self, hi, lo, vals, start, stop):
        """Copy rows [start:stop) into fresh SENTINEL/identity-padded arrays
        of the rounded batch shape."""
        b = self._round_batch(stop - start)
        n = stop - start
        p_hi = np.full(b, SENTINEL, np.uint32)
        p_lo = np.full(b, SENTINEL, np.uint32)
        p_vals = np.full((b,) + self.value_shape, self._pad_val, self.value_dtype)
        p_hi[:n] = hi[start:stop]
        p_lo[:n] = lo[start:stop]
        p_vals[:n] = vals[start:stop]
        return p_hi, p_lo, p_vals

    def _put(self, padded):
        """Ship one padded host batch toward the device; returns what
        :meth:`_merge_batch` takes.  ``flush`` times this as
        ``engine/put``."""
        return padded

    def feed(self, out: MapOutput) -> None:
        """Stage one mapped chunk; flush to device when enough rows gather.

        Host->device transfer has a fixed per-call latency, so mapped chunks
        are concatenated
        host-side and shipped in feed_batch-sized slices rather than one
        device_put per chunk — cutting round trips by the chunks-per-batch
        factor.  numpy concatenation at these sizes is microseconds.
        """
        rows = len(out)
        self.rows_fed += rows
        if rows == 0:
            return
        out.ensure_planes()  # no-op except for compact keys64-only outputs
        self._stage.append((out.hi, out.lo, out.values))
        self._staged += rows
        if self._staged >= self.feed_batch:
            self.flush()

    def flush(self) -> None:
        """Ship all staged rows to the device."""
        if not self._staged:
            return
        if len(self._stage) == 1:
            hi, lo, vals = self._stage[0]
        else:
            hi = np.concatenate([s[0] for s in self._stage])
            lo = np.concatenate([s[1] for s in self._stage])
            vals = np.concatenate([s[2] for s in self._stage])
        self._stage = []
        self._staged = 0
        try:
            # with-block, not manual enter/exit: a capacity/overflow abort
            # from the merge must still record the span (with its error
            # attribute) — the abort is exactly what a trace reader wants
            with self._span("engine/flush", rows=int(hi.shape[0])):
                for start in range(0, hi.shape[0], self.feed_batch):
                    stop = min(start + self.feed_batch, hi.shape[0])
                    with self._span("engine/put"):
                        batch = self._put(self._pad(hi, lo, vals, start,
                                                    stop))
                    with self._span("engine/dispatch"):
                        self._merge_batch(batch)
                    self._merges += 1
                    if self._merges % self._check_every == 0:
                        self._health_sync()
        finally:
            if self.obs is not None:
                self.obs.registry.count("engine/device_put_bytes",
                                        hi.nbytes + lo.nbytes + vals.nbytes)
                self.obs.registry.count("engine/flushes")

    # --- capacity growth (shared; subclasses provide the two hooks) -------

    def _incoming(self, batch_rows: int) -> int:
        """Upper bound on new live keys one padded batch can add."""
        return batch_rows

    def hint_total_keys(self, n: int) -> None:
        """Tell the engine the job-wide distinct-key count can never exceed
        ``n`` (e.g. the host dictionary's size for string-keyed workloads).
        Prevents both over-growth and the device sync a growth decision would
        otherwise need."""
        self._total_hint = n

    def hint_live_upper_bound(self, ub: int) -> None:
        """Tighten the host-side live-key bound from external exact knowledge
        (e.g. the dictionary's distinct-key count), avoiding growth syncs."""
        self._n_live_ub = min(self._n_live_ub, ub)

    def _ensure_capacity(self, incoming: int) -> None:
        if self.capacity >= self.max_capacity:
            return
        needed = self._n_live_ub + incoming
        if self._total_hint is not None:
            needed = min(needed, self._total_hint)
        if needed <= self.capacity:
            return
        if self._n_unique is not None:
            # growth looks necessary — refresh the bound from the device
            # first (the only sync on the feed path, and only at a growth
            # edge the hint couldn't rule out).  The block is a pipeline
            # stall — the host sits in it while the prefetch thread piles
            # up behind the feed — so it is its own span, feed-wait
            # evidence at the engine layer.
            with self._span("engine/growth_sync"):
                self._n_live_ub = self._read_live()
            needed = self._n_live_ub + incoming
            if self._total_hint is not None:
                needed = min(needed, self._total_hint)
        if needed <= self.capacity:
            return
        # grow to the needed power of two (not a blind 4x ladder): with a
        # distinct-key hint this lands exactly once at the right size, and a
        # tight capacity keeps the single packed finalize fetch small — the
        # fetch is capacity-proportional and the link is the scarce resource.
        # The next-pow2-above-capacity floor keeps un-hinted growth chains
        # logarithmic without overshooting a hinted exact size.
        new_cap = min(self.max_capacity,
                      max(next_pow2(needed), next_pow2(self.capacity + 1)))
        self._apply_grow(new_cap)
        _log.info("accumulator grown %d -> %d rows", self.capacity, new_cap)
        if self.obs is not None:
            self.obs.registry.count("engine/grows")
            self.obs.registry.gauge("engine/capacity_rows", new_cap)
            self.obs.tracer.instant("engine/grow", old=self.capacity,
                                    new=new_cap)
        self.capacity = new_cap

    def _health_sync(self) -> None:
        """Periodic overflow check on the feed path, as the
        ``engine/health_sync`` span: the host blocks here for the device
        (the one *mandatory* sync between merges), which is exactly the
        stall the streaming pipeline's ``feed_wait`` accounting wants
        attributed — a high ``engine/health_sync_ms`` means the device,
        not the host map, is the pipeline's limiting stage."""
        with self._span("engine/health_sync"):
            self._check_health()

    @abc.abstractmethod
    def _read_live(self) -> int:
        """Exact live-key count from the device (sync point)."""

    @abc.abstractmethod
    def _apply_grow(self, new_cap: int) -> None:
        """Extend the device accumulator with SENTINEL rows to ``new_cap``."""

    @abc.abstractmethod
    def _merge_batch(self, padded) -> None:
        """Fold one padded ``(hi, lo, vals)`` batch into device state."""

    @abc.abstractmethod
    def _check_health(self) -> None:
        """Raise if keys were dropped (host sync point)."""

    def finalize(self):
        """Flush staged rows, block + health-check; return
        ``(hi, lo, vals, n_unique)`` per the engine contract (SENTINEL rows
        are padding — mask, don't slice)."""
        self.flush()
        return self._finalize()

    @abc.abstractmethod
    def _finalize(self):
        """Post-flush finalize; see :meth:`finalize`."""

    @abc.abstractmethod
    def _top_k_device(self, k: int):
        """Device top-k over the accumulator -> (hi_k, lo_k, vals_k)."""

    def top_k(self, k: int):
        """Device top-k (value-descending) over the current accumulator ->
        numpy arrays plus the distinct-key count.  Valid for ANY monoid:
        padding rows are masked to the dtype floor on device
        (ops.topk.mask_padding), so a min-monoid's dtype-MAX identity
        cannot outrank real keys.  Rows past the live count carry SENTINEL
        keys — mask on keys, not values."""
        if self.value_shape != ():
            raise ValueError("top_k requires scalar values")
        *_, n = self.finalize()
        t_hi, t_lo, t_vals = self._top_k_device(k)
        return np.asarray(t_hi), np.asarray(t_lo), np.asarray(t_vals), n


class DeviceReduceEngine(StreamingEngineBase):
    """Single-device engine: one accumulator, no collectives."""

    def __init__(
        self,
        config: JobConfig,
        reducer: Reducer,
        value_shape: tuple = (),
        value_dtype=np.int32,
        device=None,
        overflow_check_every: int = 64,
    ):
        super().__init__(config, reducer, value_shape, value_dtype,
                         overflow_check_every)
        self.device = device if device is not None else pick_device(config.backend)
        self.feed_batch = config.batch_size
        self.max_capacity = config.key_capacity
        self.capacity = min(config.initial_key_capacity, self.max_capacity)
        #: scan-batched dispatch on the packed merge path: full-size
        #: packed feed batches queue host-side and ship as ONE stacked
        #: ``(B, 3, feed_batch)`` transfer + ONE ``lax.scan`` launch
        #: retiring B merges (the fold-engine half of the dispatch-floor
        #: attack).  Only an EXPLICIT ``--dispatch-batch N>1`` batches
        #: here — 0 (auto) targets the streamed k-means dispatch, whose
        #: roofline inputs exist; the engine's feed cadence does not
        #: measure cleanly at job start.
        self.dispatch_batch = max(1, config.dispatch_batch)
        self._pack_queue: list = []
        # eager jnp fill pinned to the engine's own device: materializes in
        # place (no host buffer shipped over the slow link) and never touches
        # the default accelerator, which may be absent/unhealthy when this is
        # a CPU engine on a TPU host.  The device_put then COMMITS the arrays
        # to self.device (a no-copy move — they already live there): arrays
        # made under default_device are uncommitted, and an all-uncommitted
        # jit (e.g. a growth before the first merge) would dispatch on the
        # default accelerator again.
        with jax.default_device(self.device):
            self._acc = [
                jax.device_put(a, self.device)
                for a in make_accumulator(
                    self.capacity, self.value_shape, self.value_dtype,
                    self.combine, xp=jnp,
                )
            ]
            self._ovf = jax.device_put(jnp.zeros((), jnp.int32), self.device)

    def _round_batch(self, n: int) -> int:
        # scan-batched dispatch wants every packable slice at the ONE
        # queue shape (feed_batch): a short slice that pow2-rounds
        # below it could not stack into the compiled (B, 3, feed_batch)
        # block and would force-drain a partial queue padded with dead
        # batches — and flush's common full+tail slicing would then
        # ship up to B-1 dead transfers per flush, making B>1 strictly
        # worse than B=1.  Rounding tails to full size lets them queue;
        # the dead-batch pad is reserved for the rare forced drains
        # (read/state/finalize/non-packed feeds).
        if self.dispatch_batch > 1 and self._packable():
            return self.feed_batch
        return super()._round_batch(n)

    def _read_live(self) -> int:
        # queued packed batches haven't merged yet: drain so the exact
        # count (which REPLACES the host upper bound) reflects them
        self._drain_packs()
        return int(self._n_unique)

    def _apply_grow(self, new_cap: int) -> None:
        pad = new_cap - self.capacity
        # fill on the engine's device (no pad-sized host->device transfer),
        # committed so the concat can never dispatch on the default device
        with jax.default_device(self.device):
            p = [jax.device_put(a, self.device)
                 for a in make_accumulator(pad, self.value_shape,
                                           self.value_dtype, self.combine,
                                           xp=jnp)]
        # jitted concat: unjitted op-by-op dispatch pays one launch per op
        self._acc = list(_grow_concat(*self._acc, *p))

    def _packable(self) -> bool:
        """Scalar int32 values ride the packed single-transfer path (the
        packed merge bitcasts the value row to int32; other dtypes would be
        silently reinterpreted, so they take the plain three-plane path)."""
        return self.value_shape == () and self.value_dtype == np.dtype(np.int32)

    def _queues(self, rows: int) -> bool:
        """Whether a packed batch of ``rows`` takes the scan-batched path:
        queued host-side, shipped B per launch (_round_batch pads every
        packable slice to feed_batch under batching, so this is the only
        packable case; a stale short slice would drain the queue first —
        merge ORDER is the feed order at any B — and take the single
        program)."""
        return self.dispatch_batch > 1 and rows == self.feed_batch

    def _put(self, padded):
        hi, lo, vals = padded
        if not self._packable():
            return jax.device_put(padded, self.device)
        packed = np.empty((3, hi.shape[0]), np.uint32)
        packed[0] = hi
        packed[1] = lo
        packed[2] = vals.view(np.uint32)
        if self._queues(hi.shape[0]):
            return packed  # shipped stacked with the queue, by _drain_packs
        return jax.device_put(packed, self.device)

    def _merge_batch(self, batch) -> None:
        if not self._packable():
            self._drain_packs()
            self.feed_device(*batch, count_rows=False)
            return
        incoming = self._incoming(batch.shape[1])
        self._ensure_capacity(incoming)
        if self._queues(batch.shape[1]):
            self._pack_queue.append(batch)
            self._n_live_ub += incoming
            if len(self._pack_queue) >= self.dispatch_batch:
                self._drain_packs()
            return
        self._drain_packs()
        *self._acc, self._n_unique, self._ovf = (
            merge_packed_into_accumulator(
                *self._acc, self._ovf, batch, combine=self.combine))
        self._n_live_ub += incoming

    def _drain_packs(self) -> None:
        """Ship the queued packed batches as ONE stacked transfer + ONE
        scan launch.  A partial queue pads to the full ``B`` with dead
        batches (SENTINEL keys, identity values) so exactly one
        ``(B, 3, feed_batch)`` shape ever compiles — a dead merge is a
        bit-exact no-op on the accumulator, so outputs are identical to
        B separate merges (tests/test_dispatch_batch.py pins this and
        the zero-compile-delta sweep)."""
        if not self._pack_queue:
            return
        b = self.dispatch_batch
        real = len(self._pack_queue)
        if len(self._pack_queue) < b:
            dead = np.empty((3, self.feed_batch), np.uint32)
            dead[0] = SENTINEL
            dead[1] = SENTINEL
            dead[2] = np.full(
                self.feed_batch,
                _identity(self.combine, np.int32)).view(np.uint32)
            self._pack_queue.extend(
                [dead] * (b - len(self._pack_queue)))
        with self._span("engine/put"):
            stacked = jax.device_put(
                np.stack(self._pack_queue),  # fresh: safe to hand off
                self.device)
        self._pack_queue = []
        *self._acc, self._n_unique, self._ovf = (
            merge_packed_batch_into_accumulator(
                *self._acc, self._ovf, stacked,
                combine=self.combine,
                # per-chunk attribution counts the REAL merges, not the
                # dead pad (consistent with the comms accounting)
                observed_chunks=real,
            )
        )

    def feed_device(self, hi, lo, vals, count_rows: bool = True) -> None:
        """Merge a device-resident batch — the hand-off used by the on-device
        map path (no host staging, padding, or transfer)."""
        self._drain_packs()  # keep merge order = feed order
        incoming = self._incoming(hi.shape[0])
        self._ensure_capacity(incoming)
        if count_rows:
            self.rows_fed += hi.shape[0]
        *self._acc, self._n_unique, self._ovf = merge_into_accumulator(
            *self._acc, self._ovf, hi, lo, vals, combine=self.combine
        )
        self._n_live_ub += incoming

    def export_state(self) -> dict:
        """Host snapshot of the device reduce state (the device-map paths'
        checkpoint unit: map outputs never exist on the host there, so the
        resumable artifact is the reduced state itself)."""
        self._drain_packs()
        return {
            "acc_hi": np.asarray(self._acc[0]),
            "acc_lo": np.asarray(self._acc[1]),
            "acc_vals": np.asarray(self._acc[2]),
            "ovf": np.asarray(self._ovf),
            "n_unique": np.asarray(
                self._n_unique if self._n_unique is not None else -1),
            "n_live_ub": np.int64(self._n_live_ub),
            "rows_fed": np.int64(self.rows_fed),
        }

    def import_state(self, st: dict) -> None:
        """Restore a snapshot onto this engine's device (committed, like
        construction)."""
        self.capacity = int(st["acc_hi"].shape[0])
        self._acc = [jax.device_put(np.asarray(st[k]), self.device)
                     for k in ("acc_hi", "acc_lo", "acc_vals")]
        self._ovf = jax.device_put(
            np.asarray(st["ovf"], np.int32), self.device)
        n = int(st["n_unique"])
        self._n_unique = None if n < 0 else np.int32(n)
        self._n_live_ub = int(st["n_live_ub"])
        self.rows_fed = int(st["rows_fed"])

    def _check_health(self) -> None:
        dropped = int(self._ovf)  # host sync point
        if dropped:
            raise CapacityError(
                f"{dropped} distinct keys dropped: accumulator exceeded "
                f"key_capacity={self.max_capacity}; increase key_capacity "
                "(--shuffle-transport does not apply here: the fold "
                "accumulator bounds DISTINCT keys, not staged rows — "
                "reduce_mode='collect' is the engine family that spills)"
            )

    def _finalize(self):
        self._drain_packs()
        if self._n_unique is None:
            # no merge ever ran: the accumulator is pristine — answer from
            # the host without a device round trip
            return (np.full(self.capacity, SENTINEL, np.uint32),
                    np.full(self.capacity, SENTINEL, np.uint32),
                    np.full((self.capacity,) + self.value_shape,
                            self._pad_val, self.value_dtype), 0)
        if self._packable():
            # ONE fetch for everything: keys, values, n_unique, overflow
            packed = np.asarray(pack_accumulator_state(
                *self._acc, self._n_unique, self._ovf))
            dropped = int(packed[1, -1])
            if dropped:
                raise CapacityError(
                    f"{dropped} distinct keys dropped: accumulator exceeded "
                    f"key_capacity={self.max_capacity}; increase "
                    "key_capacity (--shuffle-transport does not apply "
                    "here: the fold accumulator bounds DISTINCT keys, not "
                    "staged rows — reduce_mode='collect' is the engine "
                    "family that spills)"
                )
            return (packed[0, :-1], packed[1, :-1],
                    packed[2, :-1].view(self.value_dtype),
                    int(packed[0, -1]))
        self._check_health()
        n = int(self._n_unique)
        return (*self._acc, n)

    def _top_k_device(self, k: int):
        hi, lo, vals = self._acc
        return top_k_pairs_jit(hi, lo, vals, k=min(k, self.capacity))

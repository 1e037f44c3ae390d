"""Multi-host (multi-process) execution: the DCN half of the distributed
backend.

SURVEY.md §2 commits to a mesh that spans hosts via ``jax.distributed``;
this module makes that claim concrete and testable without TPU pod
hardware: ``init_distributed`` wires the coordination service (Gloo
collectives on CPU, ICI/DCN on TPU — the jax programs are identical), and
:class:`DistributedReduceEngine` extends the sharded all_to_all engine so
its host feed and host syncs work when the mesh's devices belong to
several processes:

* **feed**: each process contributes only its addressable rows;
  ``jax.make_array_from_process_local_data`` assembles the global batch.
  Processes advance in lockstep — one tiny ``psum`` per round decides
  whether anyone still has rows (SPMD: every process runs the same
  program the same number of times).
* **host syncs** (live-key count, overflow check, finalize): sharded
  arrays are not fully addressable across processes, so each sync
  replicates through a jitted identity with replicated ``out_shardings``
  (an all-gather over DCN/Gloo) before ``np.asarray``.

Work partition: process ``p`` maps chunks with ``index % P == p`` — the
chunk plan is deterministic from (file size, chunk_bytes), so no
coordination is needed to divide the input.

Key *strings* live in per-process dictionaries; the global report for the
top-k winners gathers each process's resolutions THROUGH the mesh
(:func:`gather_strings`: two ``process_allgather`` rounds — lens, then
byte planes — with a cross-process collision byte-check), so the CLI
prints words, not hashes.  With ``--output``, every process writes its
hash partition (``h % P == proc``) as ``<output>.part<p>of<P>`` in the
single-process writer's exact row format — concatenating the parts and
sorting yields the byte-identical ``final_result.txt`` (the reference's
primary artifact, ``main.rs:170-182``); only the partition's *misses*
(keys this process never mapped itself) travel through one extra
gather_strings collective.

The reference has no multi-process anything (single tokio process,
``/root/reference/src/main.rs``); this is the capability the blueprint's
"distributed communication backend" row demands.
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np

from map_oxidize_tpu.api import MapOutput, SumReducer
from map_oxidize_tpu.config import JobConfig
from map_oxidize_tpu.obs import Obs
from map_oxidize_tpu.ops.hashing import SENTINEL
from map_oxidize_tpu.shuffle.base import resolve_transport
from map_oxidize_tpu.parallel.collect import (
    ShardedCollectEngine as ShardedCollectEngineBase,
)
from map_oxidize_tpu.utils.jax_compat import shard_map
from map_oxidize_tpu.utils.logging import get_logger

_log = get_logger(__name__)


def init_distributed(coordinator: str, num_processes: int, process_id: int,
                     cpu_collectives: str = "gloo") -> None:
    """Initialize the jax coordination service.  MUST run before any jax
    backend use (first jit/devices call).  On CPU platforms Gloo provides
    the cross-process collectives; on TPU pods the native ICI/DCN path is
    used and ``cpu_collectives`` is ignored."""
    import jax

    if cpu_collectives:
        try:
            jax.config.update("jax_cpu_collectives_implementation",
                              cpu_collectives)
        except Exception:  # TPU-only deployments may lack the option
            pass
    jax.distributed.initialize(coordinator, num_processes=num_processes,
                               process_id=process_id)
    _log.info("jax.distributed initialized: process %d/%d, %d global / %d "
              "local devices", jax.process_count() and process_id,
              jax.process_count(), len(jax.devices()),
              len(jax.local_devices()))


class DistributedReduceEngine:
    """Multi-process wrapper around :class:`ShardedReduceEngine`.

    Composition, not inheritance, for the host-sync overrides: every
    device value read on the host is replicated first.  The wrapped
    engine's jitted merge/topk/grow executables are unchanged — the same
    XLA programs, now compiled against a mesh whose devices span
    processes.
    """

    def __init__(self, config: JobConfig, reducer=None, mesh=None,
                 exchange_method: str = "all_to_all"):
        import jax

        from map_oxidize_tpu.parallel.engine import ShardedReduceEngine
        from map_oxidize_tpu.parallel.mesh import make_mesh, replicated

        self.mesh = mesh if mesh is not None else make_mesh(
            config.num_shards, config.backend)
        self._eng = ShardedReduceEngine(
            config, reducer if reducer is not None else SumReducer(),
            mesh=self.mesh, exchange_method=exchange_method)
        # replace the host-sync reads with replicate-then-read versions
        self._eng._read_live = self._read_live
        self._eng._check_health = self._check_health
        self._rep = jax.jit(lambda x: x,
                            out_shardings=replicated(self.mesh))
        self.n_proc = jax.process_count()
        self.proc = jax.process_index()
        # rows this process contributes to each global merge
        self.local_rows = self._eng.feed_batch // self.n_proc
        if self._eng.feed_batch % self.n_proc:
            raise ValueError("feed_batch must divide by process count")
        if self._eng.S % self.n_proc:
            raise ValueError(
                f"shard count {self._eng.S} must divide by process count "
                f"{self.n_proc} (every process owns an equal mesh slice)")
        self._sharding = self._eng._sharding
        # lockstep continue-flag: a [S] ones/zeros vector summed over the
        # mesh — every process must call this the same number of times
        self._flag_sum = _make_flag_sum(self.mesh)

    # --- observability: the wrapped engine records the flush spans and
    # shuffle counters, so the bundle is handed straight through to it

    @property
    def obs(self):
        return self._eng.obs

    @obs.setter
    def obs(self, value) -> None:
        self._eng.obs = value

    # --- replicated host syncs -------------------------------------------

    def _read_live(self) -> int:
        return int(np.max(np.asarray(self._rep(self._eng._n_unique))))

    def _check_health(self) -> None:
        from map_oxidize_tpu.parallel.engine import ShuffleOverflowError

        dropped = int(np.asarray(self._rep(self._eng._overflow))[0])
        if dropped:
            raise ShuffleOverflowError(
                f"{dropped} rows dropped (bucket overflow or a shard "
                "accumulator past key_capacity)")

    # --- lockstep feed ----------------------------------------------------

    @property
    def S(self) -> int:
        return self._eng.S

    def any_remaining(self, i_have_rows: bool) -> bool:
        return _any_remaining(self, i_have_rows) > 0

    def merge_local(self, hi: np.ndarray, lo: np.ndarray,
                    vals: np.ndarray) -> None:
        """One lockstep global merge; this process contributes up to
        ``local_rows`` rows (padded with SENTINEL/zero)."""
        import jax

        n = hi.shape[0]
        if n > self.local_rows:
            raise ValueError(f"{n} rows > local_rows {self.local_rows}")
        B = self._eng.feed_batch

        def pad(a, fill, dtype):
            p = np.full(self.local_rows, fill, dtype)
            p[:n] = a
            return p

        g = [jax.make_array_from_process_local_data(self._sharding, x, (B,))
             for x in (pad(hi, SENTINEL, np.uint32),
                       pad(lo, SENTINEL, np.uint32),
                       pad(vals, self._eng._pad_val, self._eng.value_dtype))]
        self._eng.rows_fed += n
        self._eng.feed_device(*g, count_rows=False)

    # --- replicated results ----------------------------------------------

    def finalize(self):
        """Replicated ``(hi, lo, vals, n_unique)`` — addressable on every
        process."""
        self._check_health()
        e = self._eng
        if e._n_unique is None:
            return (np.full(e.capacity * e.S, SENTINEL, np.uint32),
                    np.full(e.capacity * e.S, SENTINEL, np.uint32),
                    np.zeros(e.capacity * e.S, np.int32), 0)
        hi, lo, vals = (np.asarray(self._rep(a)) for a in e._acc)
        n = int(np.sum(np.asarray(self._rep(e._n_unique))))
        return hi, lo, vals, n

    def top_k(self, k: int):
        t_hi, t_lo, t_vals = self._eng._topk(*self._eng._acc, k)
        return (np.asarray(t_hi), np.asarray(t_lo), np.asarray(t_vals))


class DistributedCollectEngine(ShardedCollectEngineBase):
    """Multi-process sharded collect (the inverted-index engine's DCN
    form).  Inherits the jitted route/append/sort executables — identical
    XLA programs over a mesh whose devices span processes — and overrides
    the host surface: lockstep ``merge_local`` feeds assembled with
    ``make_array_from_process_local_data``; cursor/result reads replicate
    first (sharded arrays are not fully addressable across processes).

    Beyond-RAM: each process's post-exchange hash partition (the rows its
    local mesh slice owns) is DISJOINT, so past ``max_rows`` the engine
    spills it to private disk buckets (:mod:`map_oxidize_tpu.shuffle`)
    instead of the old hard abort: the ``hybrid`` transport demotes the
    device buffers mid-job, ``disk`` routes every exchanged block to the
    buckets from round one, and ``hbm`` keeps a strict (now actionable)
    cap.  The demotion trips on the lockstep-summed GLOBAL row count —
    identical on every process by construction — so all processes switch
    programs in the same round and the collective sequence stays
    SPMD-consistent (``route_append`` before, ``route_spill`` after)."""

    #: per-process disk-bucket stage (shuffle.disk.DiskPairStage); None
    #: while rows stay device-resident
    _disk = None
    _spilled_rows_total = 0

    def __init__(self, config: JobConfig, mesh=None, **kw):
        import jax

        from map_oxidize_tpu.parallel.mesh import make_mesh, replicated

        mesh = mesh if mesh is not None else make_mesh(
            config.num_shards, config.backend)
        super().__init__(config, mesh=mesh, **kw)
        self.n_proc = jax.process_count()
        self.proc = jax.process_index()
        if self.S % self.n_proc:
            raise ValueError(
                f"shard count {self.S} must divide by process count "
                f"{self.n_proc}")
        if self.feed_batch % self.n_proc:
            raise ValueError("feed_batch must divide by process count")
        self.local_rows = self.feed_batch // self.n_proc
        self._sharding = self._row_spec  # _any_remaining's flag spec
        self._rep = jax.jit(lambda x: x,
                            out_shardings=replicated(self.mesh))
        self._flag_sum = _make_flag_sum(self.mesh)
        #: lockstep-summed global rows (every process computes the same
        #: value from the same psums) — what the demotion trips on
        self._global_rows = 0
        #: True once a rows-contributing flag round ran; guards against
        #: a driver that feeds merge_local without ever syncing the
        #: global count (the cap would silently stop existing)
        self._rows_synced = False
        self._route_spill_fn = None

    def _activate_disk_transport(self) -> None:
        """Per-process disk staging: rows still cross the process
        boundary through the mesh exchange (that is the transport's wire
        half), but each process drains the rows its local shards OWN into
        private top-bits buckets instead of device buffers."""
        import jax

        from map_oxidize_tpu.shuffle import DiskPairStage

        self._disk = DiskPairStage(
            prefix=f"moxt_dist_spill_p{jax.process_index()}_",
            obs=getattr(self, "_obs", None))

    @property
    def spilled(self) -> bool:
        return self._disk is not None or self._spilled_rows_total > 0

    @property
    def spilled_rows(self) -> int:
        if self._disk is not None:
            return self._disk.rows
        return self._spilled_rows_total

    def _cursor_max(self) -> int:
        return int(np.max(np.asarray(self._rep(self._cursor))))

    def _fetch(self, x) -> np.ndarray:
        return np.asarray(self._rep(x))

    @staticmethod
    def _addressable_rows(arr) -> dict:
        """{global shard row -> host block} for THIS process's slice of a
        dim-0-sharded array — no collective, no replication (the whole
        point of per-process spill)."""
        return {sh.index[0].start: np.asarray(sh.data)
                for sh in arr.addressable_shards}

    def any_remaining(self, i_have_rows: bool, rows: "int | None" = None
                      ) -> bool:
        total = _any_remaining(self, i_have_rows, rows)
        if rows is not None:
            self._global_rows += total
            self._rows_synced = True
        return total > 0

    def merge_local(self, hi: np.ndarray, lo: np.ndarray,
                    vals: np.ndarray) -> None:
        """One lockstep exchange round; this process contributes up to
        ``local_rows`` (term-hash, doc) pairs, SENTINEL-padded.  ``vals``
        is the (n, 2) uint32 doc-plane pair the collect feed format uses.
        Resident rounds append into the device buffers
        (``route_append``); spilled rounds exchange into a fixed block
        and drain each process's owned rows to its disk buckets
        (``route_spill``)."""
        import jax

        n = hi.shape[0]
        if n > self.local_rows:
            raise ValueError(f"{n} rows > local_rows {self.local_rows}")
        if vals.ndim != 2 or vals.shape[1] != 2 or vals.dtype != np.uint32:
            raise ValueError(
                "collect engines expect (n, 2) uint32 doc planes")
        self.rows_fed += n
        if (self._disk is None and not self._rows_synced
                and self.rows_fed > self.max_rows):
            # conservative backstop: local rows are a lower bound on the
            # global count, so a driver that never syncs it (no
            # any_remaining(..., rows=) rounds) still cannot grow the
            # device buffers unboundedly past the cap
            raise RuntimeError(
                "DistributedCollectEngine crossed max_rows="
                f"{self.max_rows} but the global row count was never "
                "synced: drive the engine through run_distributed_job, "
                "or pass rows= to any_remaining each lockstep round so "
                "the cap (and the disk demotion) can trip "
                "SPMD-consistently")
        if self._disk is None and self._transport.admit(
                self._global_rows, self.max_rows,
                "distributed pair collect (DistributedCollectEngine; "
                "sharding wider — more processes — also shrinks each "
                "process's partition)") == "demote":
            self._demote_to_disk()

        def pad(a, fill=SENTINEL, dtype=np.uint32):
            p = np.full(self.local_rows, fill, dtype)
            p[:n] = a
            return p

        planes = (pad(hi), pad(lo), pad(vals[:, 0]), pad(vals[:, 1]))
        B = self.feed_batch
        batch = tuple(
            jax.make_array_from_process_local_data(self._row_spec, x, (B,))
            for x in planes)
        import time as _time

        if self._disk is not None:
            self._route_to_spill(batch, n)
            return
        self._ensure_room()
        t0 = _time.perf_counter()
        *state, ovf = self._route_append(*self._buf, self._cursor, *batch)
        self._buf = tuple(state[:4])
        self._cursor = state[4]
        # worst case: every live row in the global batch landed on one shard
        self._cursor_ub += self.block
        self._overflows.append(ovf)
        self._record_exchange(n, t0, ovf)

    def _make_route_spill(self):
        """The spilled rounds' exchange program: route the global batch
        to owner shards (the same ``_exchange`` the resident program
        uses) and hand the received block straight back — no buffers, no
        cursor, nothing device-resident survives the round."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P

        from map_oxidize_tpu.obs.compile import observed_jit
        from map_oxidize_tpu.parallel.mesh import SHARD_AXIS
        from map_oxidize_tpu.parallel.shuffle import _exchange

        S, cap = self.S, self.bucket_cap

        def _route(hi, lo, dhi, dlo):
            vals = jnp.stack([dhi, dlo], axis=1)
            r_hi, r_lo, r_vals, ovf = _exchange(
                hi, lo, vals, S, cap, dest=self._dest_of(hi, lo),
                method=self.exchange_method)
            return (r_hi[None], r_lo[None], r_vals[:, 0][None],
                    r_vals[:, 1][None], ovf)

        spec = P(SHARD_AXIS)
        row2 = P(SHARD_AXIS, None)
        return observed_jit("shuffle/route_spill", jax.jit(shard_map(
            _route, mesh=self.mesh, in_specs=(spec,) * 4,
            out_specs=(row2,) * 4 + (P(),))),
            tag=self._program_tag())

    def _route_to_spill(self, batch, n: int) -> None:
        import time as _time

        from map_oxidize_tpu.parallel.collect import (
            join_live_pairs,
            raise_on_exchange_overflow,
        )

        if self._route_spill_fn is None:
            self._route_spill_fn = self._make_route_spill()
        t0 = _time.perf_counter()
        r_hi, r_lo, r_dhi, r_dlo, ovf = self._route_spill_fn(*batch)
        raise_on_exchange_overflow(ovf)
        self._disk.obs = self.obs
        hi_s = self._addressable_rows(r_hi)
        lo_s = self._addressable_rows(r_lo)
        dhi_s = self._addressable_rows(r_dhi)
        dlo_s = self._addressable_rows(r_dlo)
        staged = 0
        for s, hblk in sorted(hi_s.items()):
            got = join_live_pairs(hblk[0], lo_s[s][0], dhi_s[s][0],
                                  dlo_s[s][0])
            if got is None:
                continue
            staged += int(got[0].shape[0])
            self._disk.add(*got)
        if self.obs is not None and staged:
            # bounded-residency evidence: host rows resident at once
            self.obs.registry.gauge_max("shuffle/peak_staged_rows", staged)
        self._record_exchange(n, t0, ovf, program="shuffle/route_spill")

    def _demote_to_disk(self) -> None:
        """The hybrid transport's RESIDENT -> SPILLED transition.  Every
        process trips in the SAME lockstep round (the trip reads the
        psum-summed ``_global_rows``), drains the rows its local mesh
        slice owns from the device buffers into its private disk buckets
        — a purely local read, the partitions are disjoint — and frees
        the buffers.  Subsequent rounds run ``route_spill``."""
        from map_oxidize_tpu.parallel.collect import join_live_pairs
        from map_oxidize_tpu.shuffle import record_demotion

        self._check_exchange_overflows()
        _log.info(
            "distributed collect crossed max_rows=%d globally; process "
            "%d demotes its shard partition to per-process disk buckets",
            self.max_rows, self.proc)
        self._activate_disk_transport()
        self._disk.obs = self.obs
        with record_demotion(self.obs, self.rows_fed, "hbm", "disk",
                             shards=self.S, processes=self.n_proc,
                             max_rows=self.max_rows):
            if self._buf is not None:
                hi_s, lo_s, dhi_s, dlo_s = [self._addressable_rows(x)
                                            for x in self._buf]
                cur = self._addressable_rows(self._cursor)
                for s, hblk in sorted(hi_s.items()):
                    c = int(cur[s][0])
                    if c <= 0:
                        continue
                    got = join_live_pairs(hblk[0][:c], lo_s[s][0][:c],
                                          dhi_s[s][0][:c],
                                          dlo_s[s][0][:c])
                    if got is None:
                        continue
                    self._disk.add(*got)
                self._buf = None
                self._cursor = None
                self._cursor_ub = 0

    def finalize(self):
        if self.spilled:
            raise RuntimeError(
                "per-process spill is active; use finalize_spilled_csr")
        return super().finalize()

    def finalize_spilled_csr(self):
        """Bucket-by-bucket CSR finalize of THIS process's disk
        partition (the shared
        :meth:`~map_oxidize_tpu.shuffle.disk.DiskPairStage.drain_csr`).
        The intra-bucket sort is the full (key, doc) lexsort: rows from
        different processes' chunks interleave arbitrarily per term, so
        the single-controller path's feed-order-stability argument does
        not apply — and the lexsort restores oracle order exactly
        because (term, doc) pairs are distinct by construction.  Terms
        come out globally hash-ascending (buckets are top-bit ranges);
        resident memory is one bucket at a time."""
        if self._disk is None:
            raise RuntimeError("engine did not spill; use finalize")
        self._check_exchange_overflows()
        self._spilled_rows_total = self._disk.rows
        terms, offsets, docs, holder, peak = self._disk.drain_csr(
            self._sort_kd)
        self._disk = None
        if self.obs is not None and peak:
            self.obs.registry.gauge_max("shuffle/peak_staged_rows", peak)
        return terms, offsets, docs, holder

    def _sort_kd(self, keys, docs):
        """The spilled drain's intra-bucket sort: always the full
        (key, doc) lexsort (cross-process interleave, see
        :meth:`finalize_spilled_csr`); under ``pair_order='lex'`` the
        doc plane compares UNSIGNED (dataflow payloads are arbitrary
        u64 bit patterns — an i64 view would order the top-bit half
        first; doc ids are never negative, so the ii path is
        unchanged either way)."""
        d = docs.view(np.uint64) if self.pair_order == "lex" else docs
        order = np.lexsort((d, keys))
        return keys[order], docs[order]

    def finalize_spilled_runs(self):
        """Sorted-run drain of THIS process's disk partition (the
        distributed sort's spilled finalize): yields lexsorted
        ``(keys, docs)`` blocks in ascending top-bit bucket order.
        Under a range partition the process's shards own a contiguous
        key range, so its drained blocks concatenate sorted — and the
        per-process part files concatenate, process-major, into the
        globally sorted artifact."""
        if self._disk is None:
            raise RuntimeError("engine did not spill; use finalize")
        self._check_exchange_overflows()
        self._spilled_rows_total = self._disk.rows
        disk, self._disk = self._disk, None
        return disk.drain_sorted(self._sort_kd)

    def feed(self, out):  # pragma: no cover - contract guard
        raise NotImplementedError(
            "DistributedCollectEngine is fed via merge_local (lockstep); "
            "single-process feed() would deadlock the other processes")


def _make_flag_sum(mesh):
    import jax
    from functools import partial

    from jax.sharding import PartitionSpec as P

    from map_oxidize_tpu.obs.compile import observed_jit
    from map_oxidize_tpu.parallel.mesh import SHARD_AXIS

    return observed_jit("dist/flag_psum", jax.jit(shard_map(
        partial(jax.lax.psum, axis_name=SHARD_AXIS),
        mesh=mesh, in_specs=P(SHARD_AXIS), out_specs=P())))


def _any_remaining(engine, i_have_rows: bool,
                   rows: "int | None" = None) -> int:
    """Global sum over processes (one tiny mesh psum): every process
    must call this once per round; a positive sum means someone still
    has rows.  With ``rows``, this process contributes its actual staged
    row count for the coming round instead of a 0/1 flag — the SAME
    compiled program on the same shapes, but the replicated sum is then
    the GLOBAL rows entering the round, which is how every process
    learns the lockstep-synchronized row count the collect engine's
    disk demotion trips on (identical everywhere, so the transition is
    SPMD-consistent).

    The round is host-synchronous (``np.asarray`` forces the psum), so
    its wall IS the collective's latency — recorded per invocation into
    the comms observatory and the ``dist/flag_wait_ms`` histogram, the
    live straggler-wait signal process 0's ``/status`` aggregate reads
    (a fast process's flag wall is time blocked on the slowest one)."""
    import time as _time

    import jax

    S = engine.S
    if rows is None:
        local = np.full(S // engine.n_proc, 1 if i_have_rows else 0,
                        np.int32)
    else:
        local = np.zeros(S // engine.n_proc, np.int32)
        local[0] = int(rows) if i_have_rows else 0
    flags = jax.make_array_from_process_local_data(
        engine._sharding, local, (S,))
    t0 = _time.perf_counter()
    out = int(np.asarray(engine._flag_sum(flags)))
    obs = engine.obs
    if obs is not None:
        wall_ms = (_time.perf_counter() - t0) * 1e3
        obs.registry.observe("dist/flag_wait_ms", wall_ms)
        # payload: the [S] int32 flag vector, summed to every shard
        obs.registry.comm("psum", "dist/flag_psum", 4 * S * S,
                          shape=(S,), latency_ms=wall_ms)
    return out


def gather_strings(hashes: "list[int]", dictionary,
                   obs=None) -> "dict[int, bytes]":
    """Resolve key bytes for ``hashes`` across every process: each process
    contributes what its local dictionary knows, gathered THROUGH the mesh
    (``process_allgather`` — no shared filesystem, no RPC side-channel).
    Two rounds: (1) per-hash byte lengths, to size the byte plane; (2) the
    padded byte planes themselves.  Disagreeing resolutions for one hash
    abort (a cross-process 64-bit collision — same guarantee the
    single-process dictionary gives).  Returns possibly-partial results:
    a hash nobody can resolve is simply absent.  Every process must call
    this with the SAME hash list (it is a collective).  With ``obs``,
    both rounds land in the comms observatory (payload + measured wall —
    the call is host-synchronous, so the wall IS the latency)."""
    import time as _time

    from jax.experimental import multihost_utils

    k = len(hashes)
    if k == 0:
        return {}
    d = dictionary.materialized()
    local = [d.get(h) for h in hashes]
    # presence is tracked separately from length (len sentinel -1 =
    # unknown-here), so a zero-length key resolves to b"" instead of
    # silently reporting unresolvable
    lens = np.array([-1 if b is None else len(b) for b in local], np.int32)
    t0 = _time.perf_counter()
    all_lens = np.asarray(multihost_utils.process_allgather(lens))
    if all_lens.ndim == 1:  # single process: allgather returns (k,)
        all_lens = all_lens[None]
    if obs is not None:
        P = all_lens.shape[0]
        obs.registry.comm("all_gather", "dist/gather_strings",
                          P * P * lens.nbytes, shape=lens.shape,
                          latency_ms=(_time.perf_counter() - t0) * 1e3)
    maxlen = int(all_lens.max())
    if maxlen < 0:
        return {}
    buf = np.zeros((k, max(maxlen, 1)), np.uint8)
    for i, b in enumerate(local):
        if b is not None and b:
            buf[i, :len(b)] = np.frombuffer(b, np.uint8)
    t0 = _time.perf_counter()
    all_buf = np.asarray(multihost_utils.process_allgather(buf))
    if all_buf.ndim == 2:
        all_buf = all_buf[None]
    if obs is not None:
        P = all_buf.shape[0]
        obs.registry.comm("all_gather", "dist/gather_strings",
                          P * P * buf.nbytes, shape=buf.shape,
                          latency_ms=(_time.perf_counter() - t0) * 1e3)
    out: dict[int, bytes] = {}
    for i, h in enumerate(hashes):
        for p in range(all_lens.shape[0]):
            ln = int(all_lens[p, i])
            if ln < 0:
                continue
            b = bytes(all_buf[p, i, :ln])
            prev = out.get(h)
            if prev is not None and prev != b:
                raise ValueError(
                    f"cross-process 64-bit collision: {prev!r} and {b!r} "
                    f"both hash to {h:#x}")
            out[h] = b
    return out


def _allgather_union(local: np.ndarray, obs=None) -> np.ndarray:
    """Global sorted-unique union of each process's u64 hash list (two
    allgather rounds: counts, then zero-padded planes).  The result is
    identical on every process, so it can feed :func:`gather_strings`
    (a collective that requires the same hash list everywhere).

    Hashes travel as (2, n) uint32 hi/lo planes: with jax's default
    x64-disabled config, ``process_allgather`` silently downcasts int64
    input to int32 — a 64-bit hash shipped directly loses its top half
    (caught driving the CLI end-to-end, round 5)."""
    from jax.experimental import multihost_utils

    from map_oxidize_tpu.ops.hashing import join_u64, split_u64

    def _ag(a):
        g = np.asarray(multihost_utils.process_allgather(a))
        return g[None] if g.ndim == a.ndim else g

    import time as _time

    local = np.asarray(local, np.uint64)
    all_n = _ag(np.array([local.shape[0]], np.int32)).reshape(-1)
    cap = int(all_n.max()) if all_n.size else 0
    if cap == 0:
        return np.empty(0, np.uint64)
    pad = np.zeros((2, cap), np.uint32)
    hi, lo = split_u64(local)
    pad[0, :local.shape[0]] = hi
    pad[1, :local.shape[0]] = lo
    t0 = _time.perf_counter()
    planes = _ag(pad)
    if obs is not None:
        P = planes.shape[0]
        obs.registry.comm("all_gather", "dist/hash_union",
                          P * P * pad.nbytes, shape=pad.shape,
                          latency_ms=(_time.perf_counter() - t0) * 1e3)
    parts = [join_u64(planes[i, 0, :int(all_n[i])],
                      planes[i, 1, :int(all_n[i])])
             for i in range(planes.shape[0])]
    return np.unique(np.concatenate(parts))


def resolve_strings_for(owned: "list[int]", dictionary,
                        obs=None) -> "dict[int, bytes]":
    """Resolve key bytes for an arbitrary DISJOINT partition of the key
    space (each process passes the hashes it owns — by ``h % P`` on the
    resident path, by owner shard on the spilled path).  Local
    dictionary first; the union of every process's misses resolves
    through one :func:`gather_strings` round.  Every process must call
    this — it is a collective — and every counted key was mapped by
    *some* process, so an unresolvable key is an engine bug and
    raises."""
    owned = [int(h) for h in owned]
    d = dictionary.materialized()
    missing = np.array([h for h in owned if h not in d], np.uint64)
    gathered = gather_strings(
        [int(h) for h in _allgather_union(missing, obs)], dictionary, obs)
    out: dict[int, bytes] = {}
    for h in owned:
        b = d.get(h)
        if b is None:
            b = gathered.get(h)
        if b is None:
            raise RuntimeError(
                f"no process could resolve key {h:#x} — its mapper "
                "dictionary should have recorded it")
        out[h] = b
    return out


def partition_strings(hashes, dictionary, proc: int, n_proc: int,
                      obs=None) -> "dict[int, bytes]":
    """Resolve key bytes for THIS process's hash partition
    (``h % n_proc == proc``) of ``hashes`` — the ``h % P`` spelling of
    :func:`resolve_strings_for` (also a collective)."""
    return resolve_strings_for(
        [int(h) for h in hashes if int(h) % n_proc == proc],
        dictionary, obs)


def _allgather_u64(vals: np.ndarray, obs=None,
                   program: str = "dist/spill_merge") -> np.ndarray:
    """``process_allgather`` of a fixed-width u64 vector -> ``(P, k)``,
    shipped as hi/lo uint32 planes (the x64-disabled downcast trap —
    see :func:`_allgather_union`).  Every process must pass the same
    ``k``."""
    import time as _time

    from jax.experimental import multihost_utils

    from map_oxidize_tpu.ops.hashing import join_u64, split_u64

    hi, lo = split_u64(np.asarray(vals, np.uint64))
    planes = np.stack([hi, lo])
    t0 = _time.perf_counter()
    g = np.asarray(multihost_utils.process_allgather(planes))
    if g.ndim == planes.ndim:
        g = g[None]
    if obs is not None:
        P = g.shape[0]
        obs.registry.comm("all_gather", program, P * P * planes.nbytes,
                          shape=planes.shape,
                          latency_ms=(_time.perf_counter() - t0) * 1e3)
    return join_u64(g[:, 0], g[:, 1])


def _allgather_i64(vals: np.ndarray, obs=None,
                   program: str = "dist/spill_merge") -> np.ndarray:
    """Signed twin of :func:`_allgather_u64` (two's-complement safe)."""
    u = _allgather_u64(np.asarray(vals, np.int64).view(np.uint64), obs,
                       program)
    return u.view(np.int64)


def _spilled_invertedindex_result(config: JobConfig, obs, engine,
                                  dictionary, records: int
                                  ) -> "DistributedResult":
    """Finalize a spilled multi-process inverted index: each process
    drains its private disk buckets into ITS partition's CSR (disjoint
    by owner shard — no process ever materializes the global pair set,
    which is the whole point), then the global facts reduce over tiny
    collectives: term/pair totals and per-process top-k candidates
    allgather (k rows per process, not the key space), winner strings
    resolve through the usual miss-union gather, and each process
    writes its partition file (``<output>.part<p>of<P>`` — partitioned
    by owner shard here, not ``h % P``; the parts still cover the key
    space disjointly, so concatenating them yields the same artifact)."""
    from map_oxidize_tpu.io.writer import write_postings_stream

    registry = obs.registry
    P_ = engine.n_proc
    with obs.phase("finalize"):
        terms, offsets, docs, holder = engine.finalize_spilled_csr()
        df = np.diff(offsets)
        k = config.top_k
        if terms.shape[0]:
            order = np.lexsort((terms, -df))[:k]
            cand_t, cand_df = terms[order], df[order]
        else:
            cand_t = np.empty(0, np.uint64)
            cand_df = np.empty(0, np.int64)
        # fixed-width candidate pads (df = -1 marks a pad row)
        pad_t = np.zeros(k, np.uint64)
        pad_df = np.full(k, -1, np.int64)
        pad_t[:cand_t.shape[0]] = cand_t
        pad_df[:cand_df.shape[0]] = cand_df
        all_t = _allgather_u64(pad_t, obs).reshape(-1)
        all_df = _allgather_i64(pad_df, obs).reshape(-1)
        live = all_df >= 0
        all_t, all_df = all_t[live], all_df[live]
        # candidate partitions are disjoint, so the global top-k is a
        # straight merge: df desc, hash asc on ties (engine convention)
        sel = np.lexsort((all_t, -all_df))[:k]
        t_hashes = [int(h) for h in all_t[sel]]
        words = gather_strings(t_hashes, dictionary, obs)
        top = [(h, words.get(h), int(c))
               for h, c in zip(t_hashes, all_df[sel])]
        totals = _allgather_i64(np.array(
            [int(terms.shape[0]), int(offsets[-1])], np.int64), obs)
        n_keys = int(totals[:, 0].sum())
        n_pairs = int(totals[:, 1].sum())
    dp = getattr(obs, "dataplane", None)
    if dp is not None:
        # the out-side was recorded per disk bucket during the CSR
        # drain (disjoint owner shards); one collective folds both
        # sides global, then the audit must balance exactly
        dp.set_records_in(records)
        dp.reduce_distributed(
            lambda v: _allgather_u64(v, obs, "dist/dataplane"),
            expect=(("map_out", "local"), ("reduce_out", "disjoint")))
        dp.resolve_hot_keys(
            gather_strings(dp.hot_hashes(), dictionary, obs).get)
        dp.check_pairs()
    if config.output_path:
        with obs.phase("write"):
            names = resolve_strings_for(terms.tolist(), dictionary, obs)
            owned = sorted((names[int(h)], j)
                           for j, h in enumerate(terms.tolist()))
            # bucket drains already sorted each term's docs ascending
            n_terms, n_bytes = write_postings_stream(
                partition_output_path(config.output_path, engine.proc, P_),
                ((term, docs[offsets[j]:offsets[j + 1]])
                 for term, j in owned))
        registry.count("dist/partition_terms_written", n_terms)
        registry.count("dist/partition_bytes_written", n_bytes)
    registry.set("spilled_pairs", int(engine.spilled_rows))
    del holder  # the doc column was fully consumed by the writer
    return DistributedResult(counts=None, top=top, n_keys=n_keys,
                             records=records, n_pairs=n_pairs)


def partition_output_path(output_path: str, proc: int, n_proc: int) -> str:
    """``<output>.part<p>of<P>`` — self-describing, no manifest needed."""
    return f"{output_path}.part{proc}of{n_proc}"


@dataclass
class DistributedResult:
    """Replicated result of a multi-process run — identical on every
    process.  ``top`` carries resolved key bytes when any process's
    dictionary knows them (``None`` for hash-only runs)."""

    counts: "dict[int, int] | None"   # wordcount/bigram: hash -> count
    top: "list[tuple[int, bytes | None, int]]"  # (hash, bytes?, value)
    n_keys: int
    records: int                      # THIS process's mapped records
    n_pairs: int = 0                  # invertedindex only
    estimate: float = 0.0             # distinct only
    centroids: "np.ndarray | None" = None  # kmeans only (replicated)
    flag_rounds: int = 0              # lockstep psum rounds paid
    flag_s: float = 0.0               # ... and their total wall-clock
    resumed_chunks: int = 0           # chunks replayed from checkpoint
    metrics: "dict | None" = None     # THIS process's registry summary
    trace: "list | None" = None       # THIS process's Chrome events
    #                                   (None when tracing was off)


def _local_chunks(config: JobConfig, proc: int, n_proc: int, doc_mode: bool,
                  skip: int = 0):
    """Yield ``(global_index, chunk_bytes_obj, base_offset)`` for this
    process's subset (index % P == proc), skipping the first ``skip`` OWNED
    chunks (checkpoint resume).  Every process iterates the same
    deterministic chunk sequence; non-owned chunks cost a page-cache read,
    not a map."""
    from map_oxidize_tpu.io.splitter import (
        iter_chunks,
        iter_doc_chunks,
        plan_chunks,
    )

    _, chunk_bytes = plan_chunks(config.input_path, config.chunk_bytes)
    it = (iter_doc_chunks(config.input_path, chunk_bytes) if doc_mode
          else iter_chunks(config.input_path, chunk_bytes))
    off = 0
    for i, chunk in enumerate(it):
        base, off = off, off + len(chunk)
        # chunk i is this process's (i // n_proc)-th owned chunk
        if i % n_proc == proc and i // n_proc >= skip:
            yield i, chunk, base


def _map_local(obs, mapper, chunks, doc_mode: bool = False):
    """Map :func:`_local_chunks` items under ``dist/map_chunk``: yields
    ``(global_index, MapOutput, end_offset)``."""
    for idx, chunk, base in chunks:
        with obs.tracer.span("dist/map_chunk", index=idx, bytes=len(chunk)):
            out = (mapper.map_docs(chunk, base) if doc_mode
                   else mapper.map_chunk(bytes(chunk)))
            out.ensure_planes()  # no-op except compact keys64 outputs
        yield idx, out, base + len(chunk)


def run_distributed_job(config: JobConfig, workload: str
                        ) -> DistributedResult:
    """Multi-process job runner: every process maps its chunk subset
    (index % P == process_id), feeds the global mesh in lockstep, and
    returns a replicated :class:`DistributedResult`.

    Workloads: ``wordcount`` / ``bigram`` (fold engine),
    ``invertedindex`` (collect engine), ``distinct`` (local HLL registers,
    one max-merge allgather).  With ``config.checkpoint_dir``, each
    process spills its mapped chunks under ``<dir>/proc_<id>`` (identity
    includes the process count and id) and resumes its own prefix.

    Observability runs the full per-process bundle (spans + counters +
    heartbeat, not just counters): each process writes a trace/metrics
    shard (``<path>.proc<i>``), process 0 merges the shards into one
    Chrome trace + skew report at job end when they share a filesystem
    (:mod:`map_oxidize_tpu.obs.merge`), and any abort passes through the
    flight recorder (``config.crash_dir``) before propagating."""
    import jax

    config.validate()
    # --- remote-staged dispatch, BEFORE any collective or engine
    # construction: the remote transport coordinates through the shared
    # filesystem only (manifest + atomic rename, shuffle/remote.py), so
    # a peer that dies mid-shuffle must not be able to wedge this
    # process inside a jax collective.  Such jobs may run WITHOUT
    # jax.distributed at all — each process a single-controller runtime
    # whose identity comes from the config fields the launcher sets.
    n_proc = jax.process_count()
    proc = jax.process_index()
    if n_proc == 1 and config.dist_num_processes > 1:
        n_proc = config.dist_num_processes
        proc = max(config.dist_process_id, 0)
    cap = int(config.collect_max_rows or 0) or (1 << 27)
    if resolve_transport(config, cap) == "remote" and n_proc > 1:
        if workload not in ("wordcount", "bigram"):
            raise ValueError(
                "the remote shuffle transport supports fold workloads "
                f"(wordcount, bigram), not {workload!r}")
        obs = Obs.from_config(config, process=proc, n_processes=n_proc)
        with obs.recording(config, workload):
            return _run_remote_staged(config, workload, obs, proc, n_proc)
    obs = Obs.from_config(config, process=jax.process_index(),
                          n_processes=jax.process_count())
    with obs.recording(config, workload):
        if workload == "distinct":
            return _run_distributed_distinct(config, obs)
        if workload == "kmeans":
            return _run_distributed_kmeans(config, obs)
        if workload in ("sort", "join", "sessionize"):
            from map_oxidize_tpu.parallel.dataflow import (
                run_distributed_dataflow,
            )

            return run_distributed_dataflow(config, workload, obs)
        return _run_distributed_core(config, workload, obs)


def _run_distributed_core(config: JobConfig, workload: str, obs: Obs
                          ) -> DistributedResult:
    import time as _time

    from map_oxidize_tpu.ops.hashing import HashDictionary, join_u64
    from map_oxidize_tpu.runtime import resolve_mapper
    from map_oxidize_tpu.runtime.stream import InputStream
    from map_oxidize_tpu.workloads.bigram import make_bigram
    from map_oxidize_tpu.workloads.wordcount import make_wordcount

    registry = obs.registry
    use_native = resolve_mapper(config, workload) == "native"
    doc_mode = workload == "invertedindex"
    from map_oxidize_tpu.runtime.driver import (
        collect_engine_kw,
        record_transport,
        solved_exchange,
        solved_transport,
    )

    transport = solved_transport(config, obs)
    push_mode = transport == "pipelined"
    exchange = solved_exchange(config, obs)
    if workload in ("wordcount", "bigram"):
        maker = make_wordcount if workload == "wordcount" else make_bigram
        mapper, reducer = maker(config.tokenizer, use_native)
        engine = DistributedReduceEngine(config, reducer,
                                         exchange_method=exchange)
    elif workload == "invertedindex":
        from map_oxidize_tpu.workloads.inverted_index import (
            make_inverted_index,
        )

        mapper = make_inverted_index(config.tokenizer, config.use_native)
        engine = DistributedCollectEngine(config, transport=transport,
                                          exchange_method=exchange,
                                          **collect_engine_kw(config))
    else:
        raise ValueError(f"unknown distributed workload {workload!r}")
    engine.obs = obs
    record_transport(obs, engine, push_mode)
    P_ = engine.n_proc
    dictionary = HashDictionary()
    # data-plane audit over the GLOBAL shard partition: every process
    # digests the rows it maps; the in-side vectors allgather-reduce at
    # finalize so conservation is proven per hash partition end to end
    from map_oxidize_tpu.obs import dataplane as _dp

    dp = obs.ensure_dataplane(
        engine.S,
        conserves=(not doc_mode and reducer.combine == "sum"
                   and getattr(mapper, "conserves_counts", True)))

    # --- the input stream, with a per-process checkpoint substore: chunk
    # ownership is part of the job identity (a resume under a different
    # process count would replay chunks this process no longer owns)
    stream = InputStream(config, obs, mapper, workload, push=push_mode,
                         subdir=f"proc_{engine.proc}",
                         extra={"dist_processes": P_,
                                "dist_process_id": engine.proc})
    staged_outs: list = []
    staged = 0

    def _produce():
        """The checkpointed prefix (lazily), then this process's fresh
        chunks, cut only after the replay, which may stop short of its
        saved prefix on a corrupt tail.  Processes advance in lockstep,
        so a chunk's end offset tracks GLOBAL progress through the file."""
        yield from stream.saved()
        owned = _local_chunks(config, engine.proc, P_, doc_mode,
                              stream.resume_k)
        yield from stream.saving(enumerate(
            (out, end) for _, out, end in _map_local(obs, mapper, owned,
                                                     doc_mode)))

    # --- push cadence: under the pipelined transport the producer runs
    # ahead of the lockstep exchange — chunk k+1 maps on the prefetcher
    # thread while round k's flag-psum + merge_local occupy this one;
    # pipeline/shuffle_overlap_ratio reports how much of the feed hid
    # behind the exchange.
    source = stream.prefetch(_produce()) if push_mode else _produce()

    # --- map-side combiner: sum-combine (min/max alike) partial fold
    # states per push window before they stage.  The data-plane audit
    # digests the RAW rows first — conservation checksums are
    # sum-combine-invariant, so the audit stays green while comms/*
    # bytes drop.  Pair mode carries (doc, pos) payloads; never combined.
    from map_oxidize_tpu.shuffle.pipelined import (
        COMBINABLE,
        combine_map_output,
        record_push_combine,
    )

    do_combine = (not doc_mode
                  and config.push_combine != "off"
                  and (config.push_combine == "on" or push_mode)
                  and reducer.combine in COMBINABLE)

    def _window() -> MapOutput:
        """The staged outputs as one window, sum-combined where that folds
        rows away (an identity window recounts nothing): the native mapper
        already folds WITHIN a chunk, so the reduction that matters happens
        here, across the window, just before rows travel.  The audit
        digested the raw rows at staging — the weighted checksum is
        sum-combine-invariant, so conservation holds."""
        outs = staged_outs or [MapOutput(
            hi=np.empty(0, np.uint32), lo=np.empty(0, np.uint32),
            values=np.empty((0, 2) if doc_mode else 0,
                            np.uint32 if doc_mode else np.int32),
            records_in=0)]
        win = MapOutput(hi=np.concatenate([o.hi for o in outs]),
                        lo=np.concatenate([o.lo for o in outs]),
                        values=np.concatenate([np.asarray(o.values)
                                               for o in outs]),
                        records_in=0)
        if do_combine and len(win):
            combined, c_in, c_out = combine_map_output(win, reducer.combine)
            if c_out < c_in:
                record_push_combine(obs, c_in, c_out)
                return combined
        return win

    def _pop_block():
        nonlocal staged
        win = _window()
        hi, lo, va = win.hi, win.lo, np.asarray(win.values)
        take = min(engine.local_rows, hi.shape[0])
        staged_outs[:] = [MapOutput(
            hi=hi[take:], lo=lo[take:], values=va[take:],
            records_in=0)]
        staged = hi.shape[0] - take
        return hi[:take], lo[:take], va[:take]

    exhausted = False
    flag_rounds = 0
    flag_s = 0.0
    with obs.phase("map+reduce"):
        while True:
            while not exhausted and staged < engine.local_rows:
                try:
                    out = next(source)
                except StopIteration:
                    exhausted = True
                    break
                dictionary.update(out.dictionary)
                if dp is not None and len(out):
                    rows = _dp.map_output_rows(out, pairs=doc_mode)
                    if rows is not None:
                        (dp.record_pairs_in if doc_mode
                         else dp.record_fold_in)(*rows)
                staged_outs.append(out)
                staged += len(out)
                if do_combine and staged >= engine.local_rows:
                    # collapse the staged window in place: if duplicates
                    # fold away, `staged` drops below local_rows and the
                    # loop keeps pulling — so the block that finally
                    # travels carries up to local_rows DISTINCT keys and
                    # the exchange-round count (the comms/*/bytes driver:
                    # each merge moves a fixed [S, cap] buffer) shrinks
                    # by the window's duplication factor.  Identity
                    # windows leave `staged` untouched and exit the loop,
                    # so re-combining cost amortizes to one sort per
                    # local_rows raw rows.
                    staged_outs[:] = [_window()]
                    staged = len(staged_outs[0])
            have = staged > 0
            t0 = _time.perf_counter()
            # round= is the lockstep sequence tag: every process runs
            # the same rounds in the same order, so round k's flag spans
            # across processes are ONE barrier — the cross-process edge
            # the critical-path DAG (obs/critpath.py) is built from
            with obs.tracer.span("dist/lockstep_flag", round=flag_rounds):
                if doc_mode:
                    # contribute the actual block size: the replicated
                    # sum is then the GLOBAL rows entering this round —
                    # the synchronized count the disk demotion trips on
                    cont = engine.any_remaining(
                        have, rows=min(staged, engine.local_rows))
                else:
                    cont = engine.any_remaining(have)
            flag_s += _time.perf_counter() - t0
            flag_rounds += 1
            if not cont:
                break
            blk = _pop_block()
            if push_mode:
                # one push round = one eagerly-exchanged block; rows
                # count what actually traveled (post-combine)
                registry.count("shuffle/push_rounds")
                registry.count("shuffle/push_rows",
                               int(blk[0].shape[0]))
            with obs.tracer.span("dist/merge_local",
                                 rows=int(blk[0].shape[0]),
                                 round=flag_rounds - 1):
                engine.merge_local(*blk)

    records = stream.records_in
    if doc_mode and getattr(engine, "spilled", False):
        result = _spilled_invertedindex_result(config, obs, engine,
                                               dictionary, records)
    elif doc_mode:
        with obs.phase("finalize"):
            keys, docs = engine.finalize()
        if dp is not None:
            dp.set_records_in(records)
            dp.reduce_distributed(
                lambda v: _allgather_u64(v, obs, "dist/dataplane"))
            # finalize() gathers the full global pair set on every
            # process, so the out-side is recorded exactly once here
            # (post-reduce — the reduction must not touch it again)
            dp.record_pairs_out(keys, docs)
            dp.resolve_hot_keys(
                gather_strings(dp.hot_hashes(), dictionary, obs).get)
            dp.check_pairs()
        # per-term doc counts from the sorted runs (term segments are
        # disjoint across shards, so run lengths are global df)
        if keys.shape[0]:
            bounds = np.flatnonzero(
                np.concatenate([[True], keys[1:] != keys[:-1]]))
            df = np.diff(np.append(bounds, keys.shape[0]))
            uniq = keys[bounds]
        else:
            uniq = np.empty(0, np.uint64)
            df = np.empty(0, np.int64)
            bounds = np.empty(0, np.int64)
        order = np.lexsort((uniq, -df))[:config.top_k]
        t_hashes = uniq[order].tolist()
        words = gather_strings(t_hashes, dictionary, obs)
        top = [(h, words.get(h), int(df[order][j]))
               for j, h in enumerate(t_hashes)]
        if config.output_path:
            # stream the partition straight from the CSR arrays: one
            # term's doc slice is resident at a time, instead of boxing
            # the whole partition into a dict of Python int lists first
            # (ADVICE r5 — the blowup the CSR design exists to avoid)
            from map_oxidize_tpu.io.writer import write_postings_stream

            with obs.phase("write"):
                names = partition_strings(uniq.tolist(), dictionary,
                                          engine.proc, P_, obs)
                ends = np.append(bounds, keys.shape[0])
                owned = sorted(
                    (names[int(h)], j) for j, h in enumerate(uniq.tolist())
                    if int(h) % P_ == engine.proc)  # term-byte output order
                n_terms, n_bytes = write_postings_stream(
                    partition_output_path(config.output_path, engine.proc,
                                          P_),
                    ((term, np.sort(docs[ends[j]:ends[j + 1]]))
                     for term, j in owned))
            registry.count("dist/partition_terms_written", n_terms)
            registry.count("dist/partition_bytes_written", n_bytes)
        result = DistributedResult(
            counts=None, top=top, n_keys=int(uniq.shape[0]),
            records=records, n_pairs=int(keys.shape[0]))
    else:
        with obs.phase("finalize"):
            hi, lo, vals, n = engine.finalize()
        live = ~((hi == np.uint32(SENTINEL)) & (lo == np.uint32(SENTINEL)))
        k64 = join_u64(hi[live], lo[live])
        if k64.shape[0] != n:
            raise RuntimeError(f"{k64.shape[0]} live keys vs n_unique {n}")
        if dp is not None:
            dp.set_records_in(records)
            dp.reduce_distributed(
                lambda v: _allgather_u64(v, obs, "dist/dataplane"))
            # the fold readback is replicated (global on every process):
            # recorded post-reduce so it is never re-summed across P
            dp.record_fold_out(k64, vals[live])
            dp.resolve_hot_keys(
                gather_strings(dp.hot_hashes(), dictionary, obs).get)
            dp.check_fold()
        counts = dict(zip(k64.tolist(), vals[live].tolist()))
        if len(counts) != n:
            # a duplicated live key means an exchange/engine bug split one
            # key's count across rows — abort, never merge (same invariant
            # as the single-controller readback's np.unique check)
            raise RuntimeError(
                f"engine emitted duplicate live keys: {n} rows, "
                f"{len(counts)} distinct")
        t_hi, t_lo, t_vals = engine.top_k(config.top_k)
        t64 = join_u64(t_hi, t_lo)
        tlive = t64 != np.uint64(0xFFFFFFFFFFFFFFFF)
        t_hashes = t64[tlive].tolist()
        words = gather_strings(t_hashes, dictionary, obs)
        top = [(h, words.get(h), c)
               for h, c in zip(t_hashes, t_vals[tlive].tolist())]
        if config.output_path:
            from map_oxidize_tpu.io.writer import write_final_result

            with obs.phase("write"):
                names = partition_strings(list(counts), dictionary,
                                          engine.proc, P_, obs)
                write_final_result(
                    partition_output_path(config.output_path, engine.proc,
                                          P_),
                    ((b, counts[h]) for h, b in names.items()))
        result = DistributedResult(counts=counts, top=top, n_keys=n,
                                   records=records)
    result.flag_rounds, result.flag_s = flag_rounds, flag_s
    result.resumed_chunks = stream.resume_k
    stream.finish()
    registry.set("records_in", records)
    registry.set("flag_rounds", flag_rounds)
    registry.set("device_rows_fed",
                 engine._eng.rows_fed if hasattr(engine, "_eng")
                 else engine.rows_fed)
    result.metrics, result.trace = finish_distributed_obs(obs, config,
                                                          workload)
    _log.info("distributed %s: %d processes, %d local records, %d keys, "
              "%d lockstep flag rounds (%.3fs)", workload, P_, records,
              result.n_keys, flag_rounds, flag_s)
    return result


def _run_remote_staged(config: JobConfig, workload: str, obs: Obs,
                       proc: int, n_proc: int) -> DistributedResult:
    """Fold workloads over the remote-staged transport
    (:mod:`map_oxidize_tpu.shuffle.remote`): map + map-side combine +
    stage to the shared filesystem, then a collective-free drain.

    The lockstep loop and its flag-psum are deliberately ABSENT — every
    cross-process edge here is a manifest on the shared filesystem, so a
    peer SIGKILLed mid-shuffle cannot wedge this process inside a
    collective.  After staging, each process waits (bounded by
    ``remote_stage_timeout_s``) for peers' ``final`` manifests; a peer
    that never goes final is claimed by exactly one survivor
    (``claim.proc<d>``, O_CREAT|O_EXCL), which re-maps the chunks absent
    from the dead peer's last committed manifest into a recovery stage.
    Every process then drains all partitions (replicated
    :class:`DistributedResult`, same contract as the lockstep core),
    verifies each against the manifest-summed weighted checksum — the
    PR 16 conservation identity carried by files instead of an
    allgather — and writes the output partitions it is responsible for
    (its own, plus any dead peer's it claimed)."""
    import os
    import time as _time

    from map_oxidize_tpu.obs.dataplane import ConservationError, mix64
    from map_oxidize_tpu.ops.hashing import HashDictionary, join_u64
    from map_oxidize_tpu.runtime import resolve_mapper
    from map_oxidize_tpu.shuffle.pipelined import (
        COMBINABLE,
        combine_map_output,
        record_push_combine,
    )
    from map_oxidize_tpu.shuffle.remote import (
        RemoteStage,
        claim_dead_proc,
        read_manifest,
        read_partition,
        read_strings,
        stage_root,
        wait_for_finals,
    )
    from map_oxidize_tpu.workloads.bigram import make_bigram
    from map_oxidize_tpu.workloads.wordcount import make_wordcount

    registry = obs.registry
    registry.set("shuffle/transport", "remote")
    use_native = resolve_mapper(config, workload) == "native"
    maker = make_wordcount if workload == "wordcount" else make_bigram
    mapper, reducer = maker(config.tokenizer, use_native)
    ufunc = COMBINABLE[reducer.combine]
    do_combine = (config.push_combine != "off"
                  and reducer.combine in COMBINABLE)
    root = stage_root(config)
    os.makedirs(root, exist_ok=True)

    def _stage_owned(owner: int, skip_chunks: "set[int]",
                     stage: RemoteStage) -> "tuple[HashDictionary, int]":
        """Map + combine + stage every chunk ``owner`` owns that is not
        already manifest-committed; returns the strings dictionary and
        record count of what THIS call mapped."""
        dictionary = HashDictionary()
        records = 0
        chunks = (c for c in _local_chunks(config, owner, n_proc, False)
                  if c[0] not in skip_chunks)
        for idx, out, end in _map_local(obs, mapper, chunks):
            dictionary.update(out.dictionary)
            records += out.records_in
            if do_combine and len(out):
                out, c_in, c_out = combine_map_output(out, reducer.combine)
                record_push_combine(obs, c_in, c_out)
            k64 = (out.keys64 if out.keys64 is not None
                   else join_u64(out.hi, out.lo))
            va = (np.ones(len(out), np.int64) if out.values is None
                  else np.asarray(out.values))
            with obs.tracer.span("shuffle/remote_stage", index=idx,
                                 rows=int(k64.shape[0])):
                # strings BEFORE the chunk commit: a committed chunk's
                # keys must be resolvable even if this process dies on
                # the very next instruction (dupes across chunks are
                # harmless — read_strings last-writes the same bytes)
                stage.stage_strings(out.dictionary)
                stage.append_chunk(idx, k64, va, records=out.records_in)
            if obs.heartbeat is not None:
                obs.heartbeat.update(rows=out.records_in, bytes_done=end)
        stage.finish()
        return dictionary, records

    with obs.phase("map+stage"):
        _, records = _stage_owned(proc, set(),
                                  RemoteStage(root, proc, n_proc, obs=obs))

    # --- the filesystem rendezvous: peers' final manifests, or takeover
    responsible = {proc}
    with obs.phase("stage_wait"):
        manifests, dead = wait_for_finals(
            root, n_proc, proc, config.remote_stage_timeout_s)
    manifests[proc] = read_manifest(root, proc)
    for d in dead:
        if claim_dead_proc(root, d, proc):
            _log.warning("process %d claimed dead peer %d: re-mapping "
                         "its un-staged chunks", proc, d)
            registry.count("shuffle/remote_takeovers")
            done = set((manifests.get(d) or {}).get("chunks_done", ()))
            with obs.phase("recover"):
                _stage_owned(d, done,
                             RemoteStage(root, proc, n_proc, obs=obs,
                                         owner=d))
            responsible.add(d)
        else:
            # another survivor won the claim; wait for ITS recovery
            # manifest to go final before draining (its re-mapped rows
            # feed every partition, including ours)
            deadline = (_time.monotonic()
                        + max(config.remote_stage_timeout_s, 1.0))
            while True:
                rec = read_manifest(root, d, recovery=True)
                if rec is not None and rec.get("final"):
                    break
                if _time.monotonic() >= deadline:
                    raise RuntimeError(
                        f"peer {d} died and its claimant never finished "
                        "recovery within the stage timeout")
                _time.sleep(0.25)
    for d in dead:
        rec = read_manifest(root, d, recovery=True)
        if rec is not None:
            manifests[n_proc + d] = rec  # distinct key; drains sum all

    # --- collective-free drain: every partition, checksum-verified
    counts: dict = {}
    with obs.phase("drain+reduce"):
        for q in range(n_proc):
            keys, vals, want = read_partition(root, manifests, q)
            if keys.shape[0]:
                order = np.argsort(keys, kind="stable")
                ks, vs = keys[order], vals[order]
                bounds = np.flatnonzero(
                    np.concatenate([[True], ks[1:] != ks[:-1]]))
                uniq = ks[bounds]
                folded = ufunc.reduceat(vs, bounds)
                got = int((mix64(uniq) * folded.view(np.uint64))
                          .sum(dtype=np.uint64))
            else:
                uniq = np.empty(0, np.uint64)
                folded = np.empty(0, np.int64)
                got = 0
            if got != want:
                raise ConservationError(
                    f"remote-staged partition {q} drained checksum "
                    f"{got:#x} != manifest sum {want:#x}: staged rows "
                    "were lost or duplicated")
            registry.count("shuffle/remote_partitions_drained")
            counts.update(zip(uniq.tolist(),
                              (int(v) for v in folded.tolist())))
    words = read_strings(root)
    order = sorted(counts, key=lambda h: (-counts[h], h))[:config.top_k]
    top = [(h, words.get(h), counts[h]) for h in order]

    if config.output_path:
        from map_oxidize_tpu.io.writer import write_final_result

        with obs.phase("write"):
            for q in sorted(responsible):
                owned = sorted(
                    (words[h], h) for h in counts
                    if h % n_proc == q and h in words)
                write_final_result(
                    partition_output_path(config.output_path, q, n_proc),
                    ((b, counts[h]) for b, h in owned))
    # the stage directory is deliberately left in place: peers drain at
    # their own pace (no rendezvous to delete behind), and after a
    # takeover it IS the recovery evidence
    registry.set("records_in", records)
    registry.set("flag_rounds", 0)
    result = DistributedResult(
        counts=counts, top=top, n_keys=len(counts), records=records)
    result.metrics, result.trace = finish_distributed_obs(obs, config,
                                                          workload)
    _log.info("remote-staged %s: process %d/%d, %d local records, "
              "%d global keys, %d dead peers recovered", workload, proc,
              n_proc, records, len(counts), len(dead))
    return result


def finish_distributed_obs(obs: Obs, config: JobConfig, workload: str
                           ) -> "tuple[dict, list | None]":
    """The multi-process twin of ``Obs.finish``: final watermarks, the
    per-process metrics document (``<metrics_out>.proc<i>``), the trace
    shard (``<trace_out>.proc<i>``, schema :data:`obs.merge.SHARD_SCHEMA`),
    a shard barrier, process 0's auto-merge (one Chrome trace + skew
    report) when shards share a filesystem, and process 0's ledger
    append.  Returns the same ``(summary, trace_events)`` pair as
    ``Obs.finish`` — to which the degenerate single-process case
    delegates outright, so the two export paths cannot drift."""
    if obs.n_processes <= 1:
        return obs.finish(config, workload)

    from map_oxidize_tpu.obs import write_json_atomic
    from map_oxidize_tpu.obs.metrics import (
        sample_device_memory,
        sample_host_memory,
    )

    import time as _time

    from map_oxidize_tpu.obs import attrib as _attrib

    obs.end_setup()
    obs.stop_live()
    xprof_report = obs.finish_xprof()
    # the end-of-job wall attribution, same as Obs.finish: each
    # process's own decomposition (collective_wait carries its lockstep
    # share) — attrib/* gauges for the ledger/gate plus the structured
    # section this process's metrics document carries, so `obs where`
    # answers for distributed runs too
    attrib_doc = _attrib.finalize(
        obs, xprof_report,
        max(_time.time() - obs.tracer.wall_start, 1e-9))
    # score the plan (exchange decision + model error) and fold this
    # process's measurements into the calibration store — the same
    # evidence loop Obs.finish runs, so distributed jobs warm the
    # collective curves their next plan reads.  Every process merges
    # its own comms rows (the store's flock'd read-merge-write is the
    # concurrency contract); only process 0 accumulates the workload
    # wall curve, so the job counts once.
    if obs.plan is not None:
        from map_oxidize_tpu.obs import plan as _plan

        try:
            _plan.finalize(obs, obs.plan, attrib_doc)
        except Exception:  # scoring is evidence, never a job failure
            pass
    import os as _os

    corpus_bytes = 0.0
    try:
        corpus_bytes = float(_os.path.getsize(config.input_path))
    except (OSError, TypeError, AttributeError):
        pass
    obs._merge_calibration(
        xprof_report, workload=workload if obs.process == 0 else None,
        corpus_bytes=corpus_bytes, attrib_doc=attrib_doc)
    sample_host_memory(obs.registry)
    sample_device_memory(obs.registry)
    if obs.heartbeat is not None:
        obs.heartbeat.final_beat()
    P_ = obs.n_processes
    # the data-plane audit (already reduced to global figures by the
    # core's allgather) publishes its data/* gauges BEFORE the registry
    # snapshot below, so every process's metrics document — and process
    # 0's ledger entry — carries them
    data_doc = obs.finish_dataplane()
    meta = obs.stamp(config, workload)
    metrics_doc = dict(obs.registry.to_dict(), meta=meta,
                       attrib=attrib_doc)
    if obs.plan is not None:
        metrics_doc["plan"] = obs.plan
    if data_doc is not None:
        metrics_doc["data"] = data_doc
    if xprof_report is not None:
        # per-process xprof shards merge like everything else: each
        # process's metrics doc carries its own program table
        metrics_doc["xprof"] = xprof_report
    if obs.series is not None:
        metrics_doc["series"] = obs.series.export()
    if config.metrics_out:
        # one document per process (counters are per-process facts); the
        # suffix keeps P writers off one file
        write_json_atomic(f"{config.metrics_out}.proc{obs.process}",
                          metrics_doc)
    trace = obs.tracer.chrome_trace() if obs.tracer.enabled else None
    if trace is not None:
        trace.insert(0, {"name": "moxt_meta", "ph": "M",
                         "pid": obs.tracer._pid, "tid": 0, "args": meta})
    skew = None
    if trace is not None and config.trace_out != "-":
        from map_oxidize_tpu.obs.merge import shard_path, write_shard

        write_shard(shard_path(config.trace_out, obs.process), meta,
                    trace, metrics_doc)
        # Rendezvous so process 0 reads only durably-written shards.
        # Best-effort: a peer that died AFTER its last engine collective
        # never reaches this barrier, and the coordination service then
        # fails it here — this process's shard, outputs, and metrics are
        # already on disk at that point, so only the auto-merge is lost,
        # not the evidence (re-merge by hand: `obs merge <trace_out>`).
        try:
            _obs_barrier()
            if obs.process == 0:
                from map_oxidize_tpu.obs import merge as obs_merge

                skew = obs_merge.maybe_merge_at_job_end(config, 0, P_)
        except Exception as e:  # evidence must not fail the job
            _log.warning("obs shard barrier/merge failed (%s); merge by "
                         "hand: python -m map_oxidize_tpu obs merge %s",
                         e, config.trace_out)
    critpath_doc = (skew or {}).get("critpath")
    if critpath_doc and not critpath_doc.get("error"):
        # the causal headline: critpath/* gauges land BEFORE the summary
        # below, so the ledger entry (and obs diff --gate / obs trend)
        # carries them; process 0's metrics document gains the full
        # section (obs critpath reads it); one extra series sample +
        # SLO tick lets the critpath-process-blame rule see the final
        # figures (the evaluator otherwise stopped before the merge)
        from map_oxidize_tpu.obs import critpath as _critpath

        _critpath.publish(obs.registry, critpath_doc)
        if config.metrics_out:
            metrics_doc["critpath"] = critpath_doc
            metrics_doc["gauges"] = dict(
                metrics_doc.get("gauges") or {},
                **_critpath.headline(critpath_doc))
            write_json_atomic(f"{config.metrics_out}.proc{obs.process}",
                              metrics_doc)
        try:
            if obs.series is not None:
                obs.series.sample_once()
            if obs.alerts is not None:
                obs.alerts.evaluate_once()
        except Exception:  # evidence, never a job failure
            pass
    summary = obs.registry.summary()
    if obs.process == 0 and getattr(config, "ledger_dir", None):
        from map_oxidize_tpu.obs import ledger

        extra = {}
        if skew:
            extra = {"records_total": skew.get("records_total"),
                     "skew": skew.get("skew")}
        if obs.plan is not None:
            # the full plan doc rides the entry, same as Obs.finish
            extra["plan"] = obs.plan
        if critpath_doc and not critpath_doc.get("error"):
            # the compact causal summary (full segments stay in the
            # skew report next to the merged trace)
            extra["critpath"] = {
                "bound_by": critpath_doc.get("bound_by"),
                "path_over_wall_pct":
                    critpath_doc.get("path_over_wall_pct"),
                "blame": critpath_doc.get("blame"),
                "slack": critpath_doc.get("slack"),
                "what_if": critpath_doc.get("what_if"),
            }
        comms = obs.registry.comms_table()
        if comms:
            extra["comms"] = comms
        if data_doc is not None:
            from map_oxidize_tpu.obs.dataplane import ledger_section

            extra["data"] = ledger_section(data_doc)
        ledger.append(config.ledger_dir, ledger.build_entry(
            config, workload, summary, n_processes=P_, extra=extra))
    return summary, trace


def _obs_barrier() -> None:
    """Cross-process rendezvous before process 0 reads the other
    processes' shard files."""
    from jax.experimental import multihost_utils

    multihost_utils.sync_global_devices("moxt_obs_shards")


def _kmeans_ckpt_barrier() -> None:
    """Rendezvous after process 0 arbitrates the checkpoint identity
    (and possibly clears a stale snapshot) and before the other
    processes read it."""
    from jax.experimental import multihost_utils

    multihost_utils.sync_global_devices("moxt_kmeans_ckpt")


def _run_distributed_distinct(config: JobConfig, obs: Obs
                              ) -> DistributedResult:
    """Distributed HLL: each process folds its chunk subset into local
    registers; ONE allgather max-merges them (registers are a max monoid —
    the merge is exact, the estimate is the union's)."""
    import jax

    from jax.experimental import multihost_utils

    from map_oxidize_tpu.workloads.distinct import DistinctMapper, hll_estimate

    proc = jax.process_index()
    n_proc = jax.process_count()
    p = config.hll_precision
    registers = np.zeros(1 << p, np.int32)
    records = 0
    if config.checkpoint_dir:
        _log.warning("--checkpoint-dir has no effect on distributed "
                     "distinct: registers are tiny and the scan restarts "
                     "cheaply; no spill is written")
    # DistinctMapper owns the tokenizer semantics AND the graceful
    # native-unavailable fallback (stream_or_none)
    mapper = DistinctMapper(config.tokenizer, config.use_native, p)
    with obs.phase("map+reduce"):
        chunks = _local_chunks(config, proc, n_proc, False)
        for _i, out, end in _map_local(obs, mapper, chunks):
            np.maximum.at(registers, np.asarray(out.lo, np.int64),
                          np.asarray(out.values, np.int32))
            records += out.records_in
            if obs.heartbeat is not None:
                obs.heartbeat.update(rows=out.records_in, bytes_done=end)
    with obs.phase("finalize"):
        import time as _time

        t0 = _time.perf_counter()
        all_regs = np.asarray(multihost_utils.process_allgather(registers))
        if all_regs.ndim == 1:
            all_regs = all_regs[None]
        obs.registry.comm(
            "all_gather", "dist/hll_registers",
            all_regs.shape[0] ** 2 * registers.nbytes,
            shape=registers.shape,
            latency_ms=(_time.perf_counter() - t0) * 1e3)
        merged = all_regs.max(axis=0).astype(np.int32)
        est = hll_estimate(merged)
    if config.output_path and proc == 0:
        # merged registers are replicated, so one writer suffices and the
        # file is byte-identical to the single-process driver's
        from map_oxidize_tpu.workloads.distinct import write_distinct_output

        with obs.phase("write"):
            write_distinct_output(config.output_path, merged, float(est), p)
    obs.registry.set("records_in", records)
    obs.registry.set("registers_filled", int(np.count_nonzero(merged)))
    result = DistributedResult(counts=None, top=[], n_keys=0,
                               records=records, estimate=float(est))
    result.metrics, result.trace = finish_distributed_obs(obs, config,
                                                          "distinct")
    return result


def _run_distributed_kmeans(config: JobConfig, obs: Obs
                            ) -> DistributedResult:
    """Multi-process k-means: the SAME jitted psum iteration the
    single-controller sharded fit runs (:func:`parallel.kmeans.make_fit_fn`
    — one XLA program, so the paths cannot drift), with the points array
    assembled from per-process row blocks via
    ``make_array_from_process_local_data``.  Each process loads ONLY its
    contiguous row slice of the ``.npy`` (mmap — the input must be visible
    to every host, e.g. shared storage on a pod); centroids stay
    replicated, and the one ``(k, d+1)`` psum per iteration is the only
    cross-process traffic.  Returns replicated centroids; process 0 writes
    ``--output`` (identical on every process by construction).

    With ``config.checkpoint_dir`` (shared storage, like the input),
    process 0 snapshots the replicated centroids each iteration through
    the atomic checkpoint machinery and every process resumes them —
    the same continue-training semantics as the single-controller
    driver, with a lockstep start-iteration check so a non-shared dir
    fails loudly instead of silently diverging trajectories."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from map_oxidize_tpu.parallel.kmeans import make_fit_fn
    from map_oxidize_tpu.parallel.mesh import SHARD_AXIS, make_mesh

    proc = jax.process_index()
    n_proc = jax.process_count()
    pts = np.load(config.input_path, mmap_mode="r")
    if pts.ndim != 2:
        raise ValueError(f"k-means input must be (n, d); got {pts.shape}")
    n, d = pts.shape
    k = config.kmeans_k
    if n < k:
        raise ValueError(
            f"k-means needs at least kmeans_k={k} points; input has {n}")
    # deterministic init: first k points (same as the single-process driver)
    centroids = np.asarray(pts[:k], np.float32)

    mesh = make_mesh(config.num_shards, config.backend)
    S = mesh.shape[SHARD_AXIS]
    if S % n_proc:
        raise ValueError(f"shard count {S} must divide by process count "
                         f"{n_proc}")

    # --- checkpoint/resume: same iteration-boundary snapshot contract as
    # the single-controller driver (centroids fully summarize progress).
    # Process 0 WRITES the per-iteration snapshot through the shared
    # atomic checkpoint machinery; EVERY process reads it at start — the
    # checkpoint dir must be on shared storage, like the input .npy (the
    # module contract), and a lockstep allgather verifies every process
    # resumed the same iteration before any collective runs, so a
    # non-shared dir fails loudly instead of diverging trajectories.
    store = None
    start_iter = 0
    if config.checkpoint_dir:
        import hashlib

        from map_oxidize_tpu.runtime.checkpoint import CheckpointStore

        meta = CheckpointStore.job_meta(config, "kmeans", extra={
            "kmeans_k": k,
            "kmeans_mode": "dist_device",
            "kmeans_shards": S,
            "dist_processes": n_proc,
            "kmeans_backend": config.backend,
            "kmeans_precision": config.kmeans_precision,
            "kmeans_init": hashlib.sha256(
                centroids.tobytes()).hexdigest()[:16],
        })
        if proc == 0:
            # only process 0 arbitrates identity (and clears a stale
            # foreign snapshot); the others wait, then read
            store = CheckpointStore(config.checkpoint_dir, meta)
        _kmeans_ckpt_barrier()
        if proc != 0:
            store = CheckpointStore(config.checkpoint_dir, meta)
        snap = store.load_snapshot()
        if snap is not None:
            state, _d, start_iter, _nc, _x = snap
            centroids = np.asarray(state["centroids"], np.float32)
        from jax.experimental import multihost_utils

        its = np.asarray(multihost_utils.process_allgather(
            np.array([start_iter], np.int32))).reshape(-1)
        if its.size and (its.min() != its.max()):
            raise RuntimeError(
                f"distributed kmeans resume diverged: processes loaded "
                f"iterations {its.tolist()} — --checkpoint-dir must be on "
                "storage shared by every process")
        if start_iter:
            _log.info("distributed k-means resumed at iteration %d",
                      start_iter)
    # global row padding to a multiple of S (zero-weight rows never move a
    # centroid), then contiguous per-process blocks of n_pad/P rows — the
    # rows this process's mesh slice addresses
    n_pad = -(-n // S) * S
    block = n_pad // n_proc
    lo_row, hi_row = proc * block, (proc + 1) * block
    local = np.zeros((block, d), np.float32)
    take = max(0, min(hi_row, n) - lo_row)
    if take:
        local[:take] = pts[lo_row:lo_row + take]
    if config.kmeans_precision == "bf16":
        # bf16 HBM storage, same as both single-controller fit paths: the
        # per-iteration full read and the feed are the costs, and the
        # matmul operand is cast down regardless
        import ml_dtypes

        local = local.astype(ml_dtypes.bfloat16)
    w_local = np.zeros(block, np.float32)
    w_local[:take] = 1.0

    row = NamedSharding(mesh, P(SHARD_AXIS))
    rep = jax.jit(lambda x: x, out_shardings=NamedSharding(mesh, P()))
    remaining = config.kmeans_iters - start_iter
    with obs.phase("transfer"):
        p_dev = jax.make_array_from_process_local_data(row, local,
                                                       (n_pad, d))
        w_dev = jax.make_array_from_process_local_data(row, w_local,
                                                       (n_pad,))
    with obs.phase("iterate"):
        if remaining <= 0:
            # the snapshot already covers every requested iteration: the
            # snapshotted state IS the result (continue-training read,
            # same semantics as the single-controller driver)
            if remaining < 0:
                _log.warning(
                    "checkpoint has %d iterations, more than the %d "
                    "requested; returning the snapshotted state",
                    start_iter, config.kmeans_iters)
            out = centroids
        elif store is not None:
            # checkpointing steps one compiled iteration at a time:
            # points stay sharded in HBM, only the replicated (k, d)
            # centroids cross back for process 0's snapshot — the same
            # one-dispatch-per-iteration trade as kmeans_fit_sharded's
            # on_iter mode
            from map_oxidize_tpu.ops.hashing import HashDictionary

            step_fn = make_fit_fn(mesh, k, d, 1, config.kmeans_precision)
            c = jax.device_put(centroids, NamedSharding(mesh, P()))
            for i in range(remaining):
                c = step_fn(p_dev, w_dev, c)[0]
                done = start_iter + i + 1
                c_np = np.asarray(rep(c))
                if proc == 0:
                    store.save_snapshot(
                        {"centroids": np.asarray(c_np, np.float32)},
                        HashDictionary(), done, done)
                if obs.heartbeat is not None:
                    obs.heartbeat.update(
                        rows=int(take),
                        fraction=min(done / config.kmeans_iters, 1.0))
            out = np.asarray(c_np, np.float32)
        else:
            fit_fn = make_fit_fn(mesh, k, d, remaining,
                                 config.kmeans_precision)
            out = np.asarray(rep(fit_fn(
                p_dev, w_dev,
                jax.device_put(centroids, NamedSharding(mesh, P())))[0]))
    if config.output_path and proc == 0:
        from map_oxidize_tpu.workloads.kmeans import write_centroids

        with obs.phase("write"):
            write_centroids(config.output_path, out)
    ran_iters = max(remaining, 0)
    # comms accounting: one (k, d+1) partial-sums psum per iteration run
    # (the only cross-process traffic of the fit — centroids, not points)
    for _ in range(ran_iters):
        obs.registry.comm("psum", "kmeans/fit_sharded",
                          S * k * (d + 1) * 4, shape=(k, d + 1))
    if store is not None and proc == 0:
        # a zero-work run only READ the continue-training state; deleting
        # its snapshot then would destroy progress (single-controller
        # contract).  Other processes never touch the store.
        store.finish(config.keep_intermediates or ran_iters == 0)
    _log.info("distributed kmeans: %d processes, %d points, k=%d, %d "
              "iterations (%d resumed)", n_proc, n, k,
              start_iter + ran_iters, start_iter)
    obs.registry.set("records_in", int(take) * ran_iters)
    obs.registry.set("points", int(n))
    obs.registry.set("iters", start_iter + ran_iters)
    if start_iter:
        obs.registry.set("resumed_iters", start_iter)
    result = DistributedResult(counts=None, top=[], n_keys=0,
                               records=int(take) * ran_iters,
                               centroids=out)
    result.metrics, result.trace = finish_distributed_obs(obs, config,
                                                          "kmeans")
    return result


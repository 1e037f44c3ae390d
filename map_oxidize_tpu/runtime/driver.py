"""Job driver: phase orchestration.

The reference's ``main()`` runs six barriered phases — split, map, reduce,
write, report, cleanup (``/root/reference/src/main.rs:8-34``).  This driver
keeps the same observable phase contract but fuses map+reduce into one
streaming phase (host map workers feed the device engine concurrently; there
is no materialization barrier between them) and adds what the reference lacks:
config, metrics, retries, checkpointing hooks, and deterministic output.
"""

from __future__ import annotations

import time
from collections.abc import Mapping
from dataclasses import dataclass, field

import numpy as np

from map_oxidize_tpu.api import Mapper, Reducer
from map_oxidize_tpu.config import JobConfig
from map_oxidize_tpu.io.writer import (
    CountColumns,
    format_top_words,
    write_final_result,
)
from map_oxidize_tpu.obs import Obs
from map_oxidize_tpu.ops.hashing import SENTINEL, HashDictionary, join_u64
from map_oxidize_tpu.runtime.engine import DeviceReduceEngine, StreamingEngineBase
from map_oxidize_tpu.runtime.pipeline import pipelined
from map_oxidize_tpu.runtime.stream import InputStream
from map_oxidize_tpu.shuffle.base import resolve_transport
from map_oxidize_tpu.utils.logging import get_logger

_log = get_logger(__name__)


@dataclass
class JobResult:
    """What the reference reports (final_result.txt + top-10 stdout,
    main.rs:25-28), plus metrics.  ``counts`` is a read-only Mapping
    (:class:`LazyCounts`): array-backed until a consumer needs strings for
    every key.  ``trace`` carries the Chrome trace-event list when the job
    ran with tracing enabled (``config.trace_out``), else None."""

    counts: "Mapping[bytes, int]"
    top: list[tuple[bytes, int]]
    metrics: dict = field(default_factory=dict)
    trace: list | None = None

    def top_report(self, k: int) -> str:
        return format_top_words(self.top, k)


def effective_num_shards(config: JobConfig) -> int:
    """Resolve ``num_shards == 0`` to the visible device pool for the
    configured backend — the single source of truth for every caller that
    must agree with the engine actually built."""
    import jax

    n = config.num_shards
    if n == 0:
        if config.backend == "auto":
            pool = jax.devices()
        elif config.backend == "cpu":
            pool = jax.devices("cpu")
        else:
            pool = [d for d in jax.devices() if d.platform == config.backend]
            if not pool:
                raise RuntimeError(
                    f"backend={config.backend!r} but no such device; have "
                    f"{[d.platform for d in jax.devices()]}")
        n = len(pool)
    return n


def collect_engine_kw(config: JobConfig) -> dict:
    """Constructor kwargs shared by every collect-engine site: the 0
    sentinel means 'engine default', so the key is only passed when set."""
    return ({"max_rows": config.collect_max_rows}
            if config.collect_max_rows else {})


def solved_transport(config: JobConfig, obs: Obs) -> str:
    """The one route from the planner's ``shuffle_transport`` knob to a
    concrete transport name: the knob value (a pin still wins — the
    planner echoes pins verbatim) resolves through the same router the
    engines use, so driver-level cadence decisions (push pipelining,
    map-side combining) and the engine's placement agree."""
    cap = int(config.collect_max_rows or 0) or (1 << 27)
    return resolve_transport(config, cap,
                             name=obs.knob("shuffle_transport",
                                           config.shuffle_transport))


def record_transport(obs: Obs, engine, push_mode: bool) -> None:
    """Name the active shuffle transport for /status and the ledger: a
    collect engine carries one; a fold engine's is ``pipelined`` under
    the push cadence, else there is none to name."""
    name = getattr(engine, "transport", None) or (
        "pipelined" if push_mode else None)
    if name:
        obs.registry.set("shuffle/transport", name)


def solved_exchange(config: JobConfig, obs: Obs) -> str:
    """The route from the planner's ``exchange_collective`` knob to the
    concrete wire program (:data:`parallel.shuffle.EXCHANGE_COLLECTIVES`):
    the knob value (pins echoed verbatim by the planner) with the
    hard-coded ``all_to_all`` default when no plan resolved one — an
    unplanned or cold run never guesses."""
    method = obs.knob("exchange_collective", config.exchange_collective)
    return "all_to_all" if method in (None, "", "auto") else str(method)


def make_engine(config: JobConfig, reducer, value_shape=(), value_dtype=np.int32,
                wide_keys: bool = False, transport: str | None = None,
                exchange_method: str = "all_to_all"):
    """Pick the engine: shard count selects single-chip vs the all_to_all
    mesh engine, and ``reduce_mode`` (or the mapper's ``wide_keys``
    declaration under 'auto') selects the streaming fold vs the host
    collect-reduce for wide key spaces (single-chip only; the sharded
    engine hash-partitions the key space, so each shard stays narrow)."""
    n = effective_num_shards(config)
    mode = config.reduce_mode
    if mode == "auto":
        mode = ("collect" if wide_keys and n <= 1 and tuple(value_shape) == ()
                else "fold")
    elif mode == "collect" and tuple(value_shape) != ():
        _log.info("reduce_mode='collect' takes scalar values only; the "
                  "vector-valued reduce uses the fold engine")
        mode = "fold"
    if mode == "collect":
        if n > 1:
            _log.info("reduce_mode='collect' is single-chip; the %d-shard "
                      "mesh engine hash-partitions instead", n)
        else:
            from map_oxidize_tpu.runtime.host_reduce import (
                HostCollectReduceEngine,
            )

            return HostCollectReduceEngine(config, reducer,
                                           value_shape=value_shape,
                                           value_dtype=value_dtype,
                                           transport=transport,
                                           **collect_engine_kw(config))
    if n <= 1:
        return DeviceReduceEngine(config, reducer, value_shape=value_shape,
                                  value_dtype=value_dtype)
    from map_oxidize_tpu.parallel.engine import ShardedReduceEngine

    return ShardedReduceEngine(config, reducer, value_shape=value_shape,
                               value_dtype=value_dtype,
                               exchange_method=exchange_method)


class LazyCounts(Mapping):
    """{word_bytes: count} view over the engine's columnar readback.

    The per-key Python loop (hash list -> string lookup -> dict insert) is
    the finalize hot spot on wide key spaces (bigram: ~|V|^2 keys), yet most
    of what the driver needs from the counts — the total for conservation,
    the distinct-key count, the top-k — is answerable from the hash/value
    ARRAYS plus at most k string lookups.  This Mapping materializes the
    real dict only when a consumer genuinely needs strings for every key
    (writing final_result.txt, dict comparisons in tests)."""

    def __init__(self, k64: np.ndarray, vals: np.ndarray,
                 dictionary: HashDictionary):
        self._k64 = k64
        self._vals = vals
        self._dict = dictionary
        self._mat: dict[bytes, int] | None = None

    # --- array-answerable queries (no string materialization) ------------

    def __len__(self) -> int:
        return int(self._k64.shape[0])

    def total(self) -> int:
        """Σ counts, vectorized (the conservation-check input)."""
        return int(np.sum(self._vals, dtype=np.int64))

    def top_k(self, k: int) -> list[tuple[bytes, int]]:
        """Reference top-k (count desc, word asc tie-break): argpartition
        over the value column, strings materialized only for the <= k
        winners plus boundary-count ties."""
        from map_oxidize_tpu.ops.topk import top_k_candidate_indices

        if len(self) == 0:
            return []
        vals = self._vals
        cand = top_k_candidate_indices(vals, k)
        prefetch = getattr(self._dict, "prefetch", None)
        if prefetch is not None:  # hash-only mode: batch-resolve winners
            prefetch(self._k64[cand])
        lookup = self._dict.lookup
        if cand.size > max(1024, 32 * k):
            # boundary-tie flood (Zipf tail: the k-th count is a heavily
            # tied low value, e.g. 1, and the candidate set approaches the
            # whole key space).  Strict winners are < k and sort normally;
            # of the ties only the (k - strict) byte-smallest matter, which
            # heapq.nsmallest finds in O(M log need) without sorting — or
            # holding — an M-sized list.  String lookups remain one per tie
            # (the byte-order tie-break requires them).
            import heapq

            cvals = vals[cand]
            kth = cvals.min()
            strict = cand[cvals > kth]
            pairs = [(lookup(int(h)), int(v))
                     for h, v in zip(self._k64[strict].tolist(),
                                     vals[strict].tolist())]
            pairs.sort(key=lambda kv: (-kv[1], kv[0]))
            need = k - len(pairs)
            if need > 0:
                ties = cand[cvals == kth]
                words = heapq.nsmallest(
                    need, (lookup(int(h)) for h in self._k64[ties].tolist()))
                pairs += [(w, int(kth)) for w in words]
            return pairs[:k]
        pairs = [(lookup(int(h)), int(v))
                 for h, v in zip(self._k64[cand].tolist(),
                                 vals[cand].tolist())]
        pairs.sort(key=lambda kv: (-kv[1], kv[0]))
        return pairs[:k]

    def columns(self) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
        """The live keys' words and counts as columns, without building
        the dict: ``(blob u8, lens i64, counts)``, word ``i`` being
        ``blob[sum(lens[:i]):][:lens[i]]`` with count ``counts[i]`` (rows
        in the dictionary's order, not ``_k64``'s).  The keys are matched
        to the dictionary's columns by one merge of the two sorted hash
        columns; a key the dictionary lacks raises ``KeyError`` as the
        per-key lookup does.  Two keys with one word are caught where the
        words are sorted (:class:`~map_oxidize_tpu.io.writer.CountColumns`)."""
        prefetch = getattr(self._dict, "prefetch", None)
        if prefetch is not None:  # hash-only mode: one resolve-all scan
            prefetch(self._k64)
        self._dict.materialized()  # flush: the collision check runs first
        hashes, lens, blob = self._dict.to_arrays()
        by_hash = np.argsort(hashes)
        sorted_hashes = hashes[by_hash]
        by_key = np.argsort(self._k64)
        keys = self._k64[by_key]
        at = np.searchsorted(sorted_hashes, keys)
        found = at < sorted_hashes.size
        found[found] = sorted_hashes[at[found]] == keys[found]
        if not found.all():
            raise KeyError(int(keys[~found][0]))
        row = by_hash[at]
        live = np.zeros(hashes.size, bool)
        live[row] = True
        counts = np.zeros(hashes.size, self._vals.dtype)
        counts[row] = self._vals[by_key]
        if live.all():
            return blob, lens, counts
        return blob[np.repeat(live, lens)], lens[live], counts[live]

    # --- Mapping protocol (materializes) ----------------------------------

    def _materialize(self) -> dict[bytes, int]:
        if self._mat is None:
            prefetch = getattr(self._dict, "prefetch", None)
            if prefetch is not None:  # hash-only mode: one resolve-all scan
                prefetch(self._k64)
            lookup = self._dict.materialized().__getitem__
            self._mat = {lookup(h): v for h, v in
                         zip(self._k64.tolist(), self._vals.tolist())}
            if len(self._mat) != len(self._k64):
                raise RuntimeError(
                    f"readback found {len(self._mat)} distinct words for "
                    f"{len(self._k64)} live keys")
        return self._mat

    def __getitem__(self, word: bytes) -> int:
        return self._materialize()[word]

    def __iter__(self):
        return iter(self._materialize())

    def __eq__(self, other):
        if isinstance(other, LazyCounts):
            other = other._materialize()
        return self._materialize() == other

    def items(self):
        return self._materialize().items()


def _readback(engine: StreamingEngineBase, dictionary: HashDictionary
              ) -> LazyCounts:
    """Device accumulator -> :class:`LazyCounts`.  Padding rows carry the
    SENTINEL key and may sit anywhere (engine contract), so mask."""
    hi, lo, vals, n = engine.finalize()
    # the fetch blocks on the whole accumulated device chain (plus the
    # D2H copy) — consumer-visible device time the attribution ledger
    # must see, same as the streamed k-means force.  Timed AFTER
    # finalize() returns: its own dispatches/compiles are already
    # measured by the observatory, and jit compiles synchronously at
    # the call, so this window is pure execution wait + copy
    t0 = time.perf_counter()
    hi = np.asarray(hi)
    lo = np.asarray(lo)
    vals = np.asarray(vals)
    obs = getattr(engine, "obs", None)
    if obs is not None:
        obs.registry.observe("device/compute_ms",
                             (time.perf_counter() - t0) * 1e3)
    live = ~((hi == np.uint32(SENTINEL)) & (lo == np.uint32(SENTINEL)))
    k64 = join_u64(hi[live], lo[live])
    if k64.shape[0] != n:
        raise RuntimeError(
            f"readback found {k64.shape[0]} live keys but engine reported {n}"
        )
    # a duplicated live key means an engine/exchange bug split one key's
    # count across rows; the eager dict build used to catch this implicitly,
    # the lazy view must check it explicitly (vectorized, no strings)
    if np.unique(k64).shape[0] != n:
        raise RuntimeError(
            f"engine emitted duplicate live keys: {n} rows, "
            f"{np.unique(k64).shape[0]} distinct"
        )
    return LazyCounts(k64, vals[live], dictionary)


def run_wordcount_job(config: JobConfig, mapper: Mapper, reducer: Reducer,
                      workload: str = "wordcount", on_obs=None) -> JobResult:
    """End-to-end word-count-shaped job (scalar sum values, string keys).

    With ``config.checkpoint_dir`` set, every mapped chunk is spilled
    atomically and a re-run replays the spilled prefix instead of re-mapping
    it (see :mod:`map_oxidize_tpu.runtime.checkpoint`).

    Any abort — the conservation/duplicate-key/overflow invariant checks
    included — passes through the flight recorder (``obs.recording``): open
    spans close, partial metrics/trace flush, and ``config.crash_dir`` gets
    a post-mortem bundle before the exception propagates.

    ``on_obs`` (every driver takes it) hands the freshly built ``Obs``
    bundle to an embedding runtime before the body starts — the resident
    job service uses it to expose live phase/progress on ``/jobs`` and to
    deliver cancel/deadline requests (``Obs.request_cancel``)."""
    return _recorded(config, workload, on_obs, _run_wordcount_body, mapper,
                     reducer, workload)


def _recorded(config: JobConfig, workload: str, on_obs, body, *args):
    """Validate ``config``, build the job's ``Obs`` (handed to ``on_obs``
    before the body starts), and run ``body(config, obs, *args)`` under
    the flight recorder."""
    config.validate()
    obs = Obs.from_config(config)
    if on_obs is not None:
        on_obs(obs)
    with obs.recording(config, workload):
        return body(config, obs, *args)


def _run_wordcount_body(config: JobConfig, obs: Obs, mapper: Mapper,
                        reducer: Reducer, workload: str) -> JobResult:
    # the planner's shuffle_transport knob (Obs.knob seam, same as
    # pipeline_depth) picks the transport; pins still win inside the
    # resolver.  'pipelined' turns on the push cadence: the map pipeline
    # runs under the push/* span names + overlap gauge, and the map-side
    # combiner collapses each push window before the feed.
    transport = solved_transport(config, obs)
    push_mode = transport == "pipelined"
    engine = make_engine(config, reducer,
                         value_shape=mapper.value_shape,
                         value_dtype=mapper.value_dtype,
                         wide_keys=getattr(mapper, "wide_keys", False),
                         transport=transport,
                         exchange_method=solved_exchange(config, obs))
    engine.obs = obs
    record_transport(obs, engine, push_mode)
    from map_oxidize_tpu.shuffle.pipelined import (
        COMBINABLE,
        combine_map_output,
        record_push_combine,
    )

    do_combine = (config.push_combine != "off"
                  and (config.push_combine == "on" or push_mode)
                  and reducer.combine in COMBINABLE)
    # data-plane audit over the engine's hash partitions (virtual ones
    # when the engine has no shards): conservation, skew, reduction
    dp = obs.ensure_dataplane(
        getattr(engine, "S", 1),
        conserves=(reducer.combine == "sum"
                   and getattr(mapper, "conserves_counts", True)))

    # hash-only map mode: with the host collect-reduce engine the map needs
    # neither per-chunk combining nor key strings (the one final sort dedups;
    # strings resolve later by a same-cuts rescan, RescanDictionary).  Only
    # the byte-range mmap path qualifies — round-robin chunking has no byte
    # cuts for the resolver to replay.
    from map_oxidize_tpu.runtime.host_reduce import HostCollectReduceEngine

    hash_only = (getattr(mapper, "supports_hash_only", False)
                 and config.num_chunks == 0
                 and isinstance(engine, HostCollectReduceEngine))
    if hasattr(mapper, "hash_only"):
        # assign both ways: a mapper reused across jobs must not keep a
        # stale True from an earlier collect-engine run
        mapper.hash_only = hash_only
    stream = InputStream(config, obs, mapper, workload, push=push_mode,
                         round_robin=config.num_chunks > 0,
                         hash_only=hash_only)
    if hash_only:
        dictionary = mapper.rescan_dictionary(
            config.input_path, stream.chunk_bytes,
            early_stop=not config.rescan_full)
    else:
        dictionary = HashDictionary()
    span = obs.tracer.span

    def _ingest(out) -> None:
        with span("feed/dictionary"):
            dictionary.update(out.dictionary)
            if mapper.keys_have_dictionary:
                # the dictionary covers every key fed so far, so its size
                # bounds distinct keys — growth needs no device sync.
                # upper_bound self-tightens with an amortized flush when
                # pending deltas could be duplicate-dominated (see
                # HashDictionary.upper_bound).
                engine.hint_total_keys(dictionary.upper_bound())
        if dp is not None and len(out):
            from map_oxidize_tpu.obs.dataplane import map_output_rows

            with span("audit/fold_in"):
                rows = map_output_rows(out, pairs=False)
                if rows is not None:  # scalar fold rows only (not k-means)
                    dp.record_fold_in(*rows)
        if do_combine and len(out):
            # map-side combine AFTER the audit digested the raw rows:
            # the weighted checksum is sum-combine-invariant, so the
            # conservation verdict is unchanged while the feed shrinks
            out, c_in, c_out = combine_map_output(out, reducer.combine)
            record_push_combine(obs, c_in, c_out)
        with obs.feed_span(rows=len(out)):
            engine.feed(out)

    # --- resume, split (lazily — contrast main.rs:16/36-51), then map +
    # reduce as one streaming phase (main.rs:19-22 were barriered)
    stream.run(_ingest)

    # --- finalize on device; read back to host strings
    with obs.phase("finalize"):
        counts = _readback(engine, dictionary)
        top = counts.top_k(config.top_k)

    # conservation audit: every token mapped lands in exactly one count,
    # PER HASH PARTITION, with matching order-independent checksums (the
    # reference has no such invariant check; the audit generalizes the
    # old global Σ counts == Σ records_in assertion).  Only meaningful
    # for count-shaped sum workloads — a min/max monoid or a sum of
    # measurements has no such identity (conserves=False skips it).
    if dp is not None:
        with span("audit/fold_out"):
            dp.set_records_in(stream.records_in)
            dp.record_fold_out(counts._k64, counts._vals)
            dp.resolve_hot_keys(dictionary.lookup)
            dp.check_fold()
            dp.check_total(counts.total())
    elif (reducer.combine == "sum"
          and getattr(mapper, "conserves_counts", True)):
        # legacy global check — the audit's fallback when disabled
        total = counts.total()
        if stream.records_in and total != stream.records_in:
            raise RuntimeError(
                f"count conservation violated: mapped {stream.records_in} "
                f"records but reduced counts sum to {total}")

    # --- write (deterministic, atomic — fixes main.rs:170-182)
    return _finish_job(
        config, obs, workload, stream,
        lambda path: write_counts(obs, path, counts),
        JobResult(counts=counts, top=top),
        {"distinct_keys": len(counts), "device_rows_fed": engine.rows_fed})


def write_counts(obs: Obs, path: str, counts: LazyCounts) -> None:
    """``final_result.txt`` straight from the readback's columns, with
    the gauges ``write/rows`` and ``write/tie_rows`` (rows the byte-order
    sort resolved per row; 0 unless long words tie on their prefix)."""
    rows = CountColumns(*counts.columns())
    obs.registry.set("write/rows", write_final_result(path, rows))
    obs.registry.set("write/tie_rows", rows.tie_rows)


def _finish_job(config: JobConfig, obs: Obs, workload: str,
                stream: InputStream, write, result, gauges: dict):
    """Shared tail of the streamed jobs: ``write(output_path)`` under
    ``phase/write``, drop the spill (the reference always deletes,
    main.rs:194-202), record the stream's counts and ``gauges``, and
    close the job's obs into ``result``."""
    with obs.phase("write"):
        if config.output_path:
            write(config.output_path)
    stream.finish()
    for key, value in dict(records_in=stream.records_in,
                           chunks=stream.chunks, **gauges).items():
        obs.registry.set(key, value)
    result.metrics, result.trace = obs.finish(config, workload)
    if config.metrics:
        _log.info("metrics: %s", result.metrics)
    return result


@dataclass
class InvertedIndexResult:
    """Postings plus metrics (the inverted-index analogue of JobResult).
    ``postings`` is a read-only Mapping (:class:`Postings`): CSR-backed,
    materializing per-term doc lists only on access."""

    postings: "Mapping[bytes, list[int]]"
    metrics: dict = field(default_factory=dict)
    trace: list | None = None

    def top_report(self, k: int) -> str:
        lines = [f"Top {k} terms by document frequency:"]
        lines += [f"{t.decode('utf-8', 'replace')}: {df} docs"
                  for t, df in self.postings.top_by_df(k)]
        return "\n".join(lines)


def run_inverted_index_job(config: JobConfig, on_obs=None
                           ) -> InvertedIndexResult:
    """Inverted-index build (BASELINE config #4): map emits one (term, doc)
    pair per distinct term per document; the CollectEngine sorts all pairs
    once on device; postings fall out as contiguous segments.

    Output file: one line per term, ``term\\td1 d2 d3...``, terms in byte
    order — deterministic, unlike anything the reference's nondeterministic
    HashMap ordering could produce (main.rs:170-182)."""
    return _recorded(config, "invertedindex", on_obs,
                     _run_inverted_index_body)


def _run_inverted_index_body(config: JobConfig, obs: Obs
                             ) -> InvertedIndexResult:
    from map_oxidize_tpu.workloads.inverted_index import (
        Postings,
        make_inverted_index,
        postings_from_sorted,
    )

    metrics = obs.registry
    mapper = make_inverted_index(config.tokenizer, config.use_native)
    transport = solved_transport(config, obs)
    push_mode = transport == "pipelined"
    if effective_num_shards(config) > 1:
        from map_oxidize_tpu.parallel.collect import ShardedCollectEngine

        if config.collect_sort != "auto":
            _log.info("collect_sort=%r applies to the single-chip engine "
                      "only; the sharded path sorts per shard on device",
                      config.collect_sort)
        engine = ShardedCollectEngine(
            config, transport=transport,
            exchange_method=solved_exchange(config, obs),
            **collect_engine_kw(config))
    else:
        from map_oxidize_tpu.runtime.collect import CollectEngine

        engine = CollectEngine(config, transport=transport,
                               **collect_engine_kw(config))
    engine.obs = obs
    record_transport(obs, engine, push_mode)
    # data-plane audit: (term, doc) pairs must cross the collect shuffle
    # (and any spill round-trip) as an unchanged multiset
    dp = obs.ensure_dataplane(getattr(engine, "S", 1))
    dictionary = HashDictionary()

    def _ingest(out) -> None:
        dictionary.update(out.dictionary)
        if dp is not None and len(out):
            from map_oxidize_tpu.obs.dataplane import map_output_rows

            with obs.tracer.span("audit/fold_in"):
                dp.record_pairs_in(*map_output_rows(out, pairs=True))
        with obs.feed_span(rows=len(out)):
            engine.feed(out)

    # the CollectEngine feed is append-only, so per-chunk spill + replay
    # resumes it exactly like the word-count path; doc-chunk read +
    # tokenize runs ahead of the collect feed
    stream = InputStream(config, obs, mapper, "invertedindex",
                         push=push_mode)
    stream.run(_ingest, "map+collect", split_phase=False)

    with obs.phase("sort+postings"):
        if getattr(engine, "spilled", False):
            # beyond-RAM run: bucket-by-bucket CSR with an on-disk doc
            # column (memmap) — Postings answers everything lazily, so the
            # writer/report paths work unchanged with bounded residency
            terms, offsets, docs, holder = engine.finalize_spilled_csr()
            postings = Postings(terms, offsets, docs, dictionary)
            postings._spill_holder = holder  # keeps the doc file alive
            metrics.set("spilled_pairs", int(engine.spilled_rows))
            metrics.set("grouped_finalize", False)
        else:
            # the map-phase dictionary enumerates every distinct term, so
            # the host finalize can GROUP instead of SORT
            # (engine.finalize_csr: native hash->dense-id group-by, two
            # streaming passes vs six radix scatter passes); sharded /
            # device-sort engines keep the sorted-pairs path
            csr = None
            if (hasattr(engine, "finalize_csr")
                    and getattr(engine, "sort_mode", "") == "host"
                    and config.use_native
                    and len(dictionary) <= max(engine.rows_fed // 8, 1)):
                # gates mirror finalize_csr's own: don't flush/sort the
                # whole vocabulary for a device-sort or no-native run that
                # would throw it away
                d = dictionary.materialized()
                uniq = np.sort(np.fromiter(d.keys(), np.uint64,
                                           count=len(d)))
                csr = engine.finalize_csr(uniq)
            if csr is not None:
                postings = Postings(*csr, dictionary)
                if dp is not None:
                    # expand the CSR back to per-pair keys: grouping must
                    # not have dropped or invented a single (term, doc)
                    dp.record_pairs_out(
                        np.repeat(csr[0], np.diff(csr[1])), csr[2])
            else:
                keys, docs = engine.finalize()
                postings = postings_from_sorted(keys, docs, dictionary)
                if dp is not None:
                    dp.record_pairs_out(keys, docs)
            metrics.set("grouped_finalize", csr is not None)
    if dp is not None:
        dp.set_records_in(stream.records_in)
        dp.resolve_hot_keys(dictionary.lookup)
        dp.check_pairs()

    from map_oxidize_tpu.io.writer import write_postings

    return _finish_job(
        config, obs, "invertedindex", stream,
        lambda path: write_postings(path, postings),
        InvertedIndexResult(postings=postings),
        {"pairs": int(postings.n_pairs), "distinct_terms": len(postings)})


@dataclass
class KMeansResult:
    """Final centroids plus per-phase metrics (the k-means analogue of
    JobResult; there is no top-k or word dictionary to report).

    The HBM-resident fits also return each point's cluster: ``labels``,
    the ``(n,)`` int32 assignment of the last step, and ``assigned_from``,
    the ``(k, d)`` centroids it was made against (so ``centroids`` are the
    means of the points over ``labels``, an empty cluster keeping its
    ``assigned_from`` row).  ``labels`` stays a device array until the
    caller reads it (``np.asarray``), so a job that never reads it pays
    no readback.  The streamed paths, and a resume whose snapshot already
    covers every iteration, return ``None`` for both."""

    centroids: np.ndarray
    metrics: dict = field(default_factory=dict)
    trace: list | None = None
    labels: object = None
    assigned_from: np.ndarray | None = None

    def top_report(self, k: int) -> str:  # CLI-facing summary
        return (f"k-means: {self.centroids.shape[0]} centroids, "
                f"dim {self.centroids.shape[1]}")


#: fallback fit budget when the device doesn't report its memory
#: (v5-lite-class chips carry 16GB HBM; 8GB leaves headroom for XLA's
#: own buffers and the fori_loop's double-buffered carries)
_KMEANS_DEVICE_FIT_BYTES = 8 << 30


def _kmeans_device_fit_bytes(config) -> int:
    """mapper='auto' picks the HBM-resident fit when the whole working set
    fits comfortably on one device: points (n*d*4) PLUS the (n, k)
    distance and one-hot intermediates (n*k*4 each) the device step
    materializes — i.e. 4*n*(d + 2k) bytes against this budget.  The
    budget is ``config.kmeans_device_fit_bytes`` when set (the test/
    operator override pinning the beyond-fit routing, VERDICT r5 #5),
    else HALF the device's reported memory (headroom for XLA's own
    buffers and the fori_loop's double-buffered carries), falling back to
    8GB when the runtime doesn't expose memory stats (advisor r4: the
    old hardcoded 8GB assumed a 16GB chip and could OOM smaller ones).
    Beyond it, the job streams — the only option at that scale."""
    if getattr(config, "kmeans_device_fit_bytes", 0):
        return config.kmeans_device_fit_bytes
    try:
        from map_oxidize_tpu.runtime.engine import pick_device

        stats = pick_device(config.backend).memory_stats()
        total = int(stats.get("bytes_limit", 0))
        if total > 0:
            return total // 2
    except Exception:
        pass  # CPU backends and some plugins expose no memory stats
    return _KMEANS_DEVICE_FIT_BYTES


def _adopt_checkpoint_kmeans_mode(config: JobConfig,
                                  meta_wo_mode: dict) -> str | None:
    """Best-effort read of an existing snapshot's ``kmeans_mode``.

    An ``auto`` resume must land on the mode its snapshot was cut from
    even if the auto heuristic changed between versions — otherwise the
    identity mismatch would silently discard training progress.  The
    stored mode is honored only when every OTHER identity field matches
    (a stale foreign checkpoint must not flip a fresh job's mode)."""
    import json
    import os

    try:
        with open(os.path.join(config.checkpoint_dir, "meta.json")) as f:
            existing = json.load(f)
    except (OSError, ValueError):
        return None
    stored = existing.get("kmeans_mode")
    if stored not in ("device", "stream", "stream_device"):
        return None
    probe = {k: v for k, v in existing.items()
             if k not in ("kmeans_mode", "kmeans_shards", "version")}
    want = {k: v for k, v in meta_wo_mode.items()
            if k not in ("kmeans_mode", "kmeans_shards", "version")}
    return stored if probe == want else None


def run_kmeans_job(config: JobConfig, centroids: np.ndarray | None = None,
                   on_obs=None) -> KMeansResult:
    """k-means (BASELINE config #5), two execution paths:

    * HBM-resident (``mapper='device'``, and what ``'auto'`` resolves to
      whenever the points fit on device): points transfer once and every
      iteration is MXU work (distance matmul, one-hot matmul), sharded
      over the mesh with one psum per iteration when more than one device
      is visible.
    * streamed (``mapper='native'``/``'python'``, or ``'auto'`` when the
      points exceed the fit cap): ``kmeans_iters`` iterations of map (host
      assign + per-chunk partial sums) -> device vector-sum reduce; points
      never sit in host or device memory whole — the only option at
      beyond-memory scale.

    Input: a ``.npy`` float32 ``(n, d)`` points file, memory-mapped and
    streamed by row ranges.  Initial centroids default to the first
    ``kmeans_k`` points (deterministic)."""
    return _recorded(config, "kmeans", on_obs, _run_kmeans_body, centroids)


def _run_kmeans_body(config: JobConfig, obs: Obs,
                     centroids: np.ndarray | None) -> KMeansResult:
    from map_oxidize_tpu.api import SumReducer
    from map_oxidize_tpu.workloads.kmeans import (
        iter_point_chunks,
        kmeans_iteration,
    )

    metrics = obs.registry
    pts = np.load(config.input_path, mmap_mode="r")
    if pts.ndim != 2:
        raise ValueError(f"k-means input must be (n, d); got {pts.shape}")
    n, d = pts.shape
    if centroids is None:
        if n < config.kmeans_k:
            raise ValueError(
                f"k-means needs at least kmeans_k={config.kmeans_k} points "
                f"to init centroids; input has {n}")
        centroids = np.asarray(pts[:config.kmeans_k], np.float32)
    centroids = np.asarray(centroids, np.float32)
    rows = max(1, config.chunk_bytes // (4 * d))
    if config.mapper == "device":
        mode = "device"
    elif config.mapper == "auto":
        # whole device working set: points + the (n, k) distance/one-hot
        # intermediates (see _kmeans_device_fit_bytes).  Beyond the fit,
        # 'auto' streams chunks THROUGH the device
        # (kmeans_fit_streamed_device)
        fits = (4 * int(n) * (int(d) + 2 * config.kmeans_k)
                <= _kmeans_device_fit_bytes(config))
        mode = "device" if fits else "stream_device"
        if config.checkpoint_dir:
            # an existing snapshot's mode wins over the heuristic: resume
            # must continue the trajectory it was cut from
            import hashlib

            from map_oxidize_tpu.runtime.checkpoint import CheckpointStore

            stored = _adopt_checkpoint_kmeans_mode(
                config,
                CheckpointStore.job_meta(config, "kmeans", extra={
                    "kmeans_k": config.kmeans_k,
                    "kmeans_backend": config.backend,
                    "kmeans_precision": config.kmeans_precision,
                    "kmeans_init": hashlib.sha256(
                        centroids.tobytes()).hexdigest()[:16],
                }))
            if stored is not None:
                mode = stored  # "device" | "stream_device" | "stream"
    else:
        mode = "stream"
    device_mode = mode == "device"
    # streaming composes with the mesh now: stream_device shards each
    # chunk across every visible device (num_shards=1 pins one chip), so
    # the shard count is checkpoint identity for it exactly as for the
    # resident fit
    n_shards = (effective_num_shards(config)
                if mode in ("device", "stream_device") else 1)
    metrics.set("kmeans_mode", mode)
    metrics.set("kmeans_shards", n_shards)

    # --- checkpoint/resume: the iteration boundary is k-means's natural
    # materialization barrier (centroids fully summarize progress), so the
    # resumable artifact is one atomic snapshot of (centroids, iterations
    # done), superseded each iteration.  kmeans_iters is deliberately NOT in
    # the identity: a snapshot at iteration i resumes any same-job run
    # asking for >= i iterations ("continue training"); k, mode, and shard
    # count ARE identity (they change the float accumulation order).
    # NOTE a successful run DELETES its snapshot (same cleanup contract as
    # every workload; tested): continue-training past a COMPLETED run
    # requires --keep-intermediates on the earlier run.  Only interrupted
    # runs and zero-work reads keep the snapshot implicitly.
    store = None
    start_iter = 0
    if config.checkpoint_dir:
        from map_oxidize_tpu.ops.hashing import HashDictionary
        from map_oxidize_tpu.runtime.checkpoint import CheckpointStore

        import hashlib

        store = CheckpointStore(
            config.checkpoint_dir,
            CheckpointStore.job_meta(config, "kmeans", extra={
                "kmeans_k": config.kmeans_k,
                "kmeans_mode": mode,
                "kmeans_shards": n_shards,
                # backend changes float accumulation order (CPU XLA vs MXU)
                # exactly like mode/shards do, so it is identity too
                "kmeans_backend": config.backend,
                # precision moves assignment boundaries — identity as well
                "kmeans_precision": config.kmeans_precision,
                # the digest pins the INITIAL centroids: a caller-provided
                # init different from the snapshot's trajectory must
                # invalidate, not be silently overridden
                "kmeans_init": hashlib.sha256(
                    centroids.tobytes()).hexdigest()[:16],
            }))
        snap = store.load_snapshot()
        if snap is not None:
            state, _d, start_iter, _n, _x = snap
            centroids = np.asarray(state["centroids"], np.float32)
            _log.info("k-means resumed at iteration %d", start_iter)

        def _save(done: int, c: np.ndarray) -> None:
            store.save_snapshot({"centroids": np.asarray(c, np.float32)},
                                HashDictionary(), done, done)

    def _iter_done(i: int, c: np.ndarray | None = None) -> None:
        """Per-iteration hook shared by every k-means path: heartbeat tick
        (iteration fraction, since bytes mean nothing here) + optional
        snapshot.  Passed as ``on_iter`` only when a consumer exists —
        the callback forces a per-iteration centroid fetch the
        no-checkpoint no-progress run must not pay."""
        if obs.heartbeat is not None:
            obs.heartbeat.update(
                rows=int(n),
                fraction=min((start_iter + i) / config.kmeans_iters, 1.0))
        if store is not None and c is not None:
            _save(start_iter + i, c)

    want_iter_cb = store is not None or obs.heartbeat is not None
    fit = None  # the resident fits' KMeansFit
    with obs.phase("iterate"):
        remaining = config.kmeans_iters - start_iter
        if remaining <= 0:
            # snapshot already covers every requested iteration; the
            # snapshot state IS the result (continue-training semantics —
            # use a fresh checkpoint_dir to recompute from scratch)
            if remaining < 0:
                _log.warning(
                    "checkpoint has %d iterations, more than the %d "
                    "requested; returning the snapshotted state",
                    start_iter, config.kmeans_iters)
        elif mode == "stream_device":
            from map_oxidize_tpu.parallel.kmeans import kmeans_fit_streamed

            from map_oxidize_tpu.runtime.engine import pick_device

            # dispatch amortization lives in B, not in the chunk size:
            # a scan-batched launch retires B chunks, so
            # config.chunk_bytes is honored verbatim and small chunks
            # batch into full-size launches
            # (finer staging granularity, same bytes per launch).
            # Chunking deliberately does NOT depend on dispatch_batch:
            # the per-logical-chunk comms identity (and with it the
            # comms/*/bytes ledger gate) compares across B only because
            # the chunk count is B-invariant.  The divisor budgets the
            # per-chunk DEVICE working set — the points block plus the
            # (chunk, k) distance and one-hot intermediates — the same
            # 4*(d + 2k) accounting as the fit heuristic, else a
            # large-k job would OOM the chip with the very path meant
            # to avoid that.  (Per CHUNK, not per shard: the budget is
            # conservative for a multi-device mesh, where each shard
            # sees chunk_rows/S of it.)
            chunk_rows = max(1, config.chunk_bytes
                             // (4 * (int(d) + 2 * config.kmeans_k)))
            timings: dict = {}
            kw = dict(iters=remaining, chunk_rows=chunk_rows,
                      precision=config.kmeans_precision, timings=timings,
                      on_iter=_iter_done if want_iter_cb else None,
                      pipeline_depth=obs.knob("pipeline_depth",
                                              config.pipeline_depth),
                      obs=obs,
                      # B is deliberately NOT checkpoint identity (see
                      # the meta above): outputs are bit-identical at
                      # any B, so a snapshot written at one B resumes
                      # under any other (tests/test_dispatch_batch.py)
                      dispatch_batch=config.dispatch_batch)
            if n_shards > 1:
                # streaming x sharding composed: each chunk's put splits
                # across the mesh and the step is the shared one-psum
                # program (parallel/kmeans.make_stream_step_fn)
                centroids = kmeans_fit_streamed(
                    config.input_path, centroids,
                    num_shards=config.num_shards, backend=config.backend,
                    **kw)
            else:
                centroids = kmeans_fit_streamed(
                    config.input_path, centroids,
                    device=pick_device(config.backend), **kw)
            for tk, tv in timings.items():
                # the prefetcher's overlap evidence lands under the SAME
                # keys/units every other pipelined path uses
                if tk == "overlap_ratio":
                    metrics.set("pipeline/overlap_ratio", tv)
                elif tk == "feed_wait_s":
                    # already live-fed per block by the stager (the
                    # attribution bucket feed); counting the total here
                    # again would double it
                    pass
                elif tk == "dispatch_batch":
                    pass  # already recorded as the dispatch/* gauges
                else:
                    metrics.set(f"time/{tk}", round(tv, 4))
        elif device_mode:
            on_iter = _iter_done if want_iter_cb else None
            if n_shards > 1:
                from map_oxidize_tpu.parallel.kmeans import kmeans_fit_sharded

                fit = kmeans_fit_sharded(
                    pts, centroids, iters=remaining,
                    num_shards=config.num_shards, backend=config.backend,
                    on_iter=on_iter, precision=config.kmeans_precision,
                    obs=obs)
            else:
                from map_oxidize_tpu.workloads.kmeans import kmeans_fit_device

                from map_oxidize_tpu.runtime.engine import pick_device

                fit = kmeans_fit_device(
                    pts, centroids, iters=remaining,
                    device=pick_device(config.backend), on_iter=on_iter,
                    precision=config.kmeans_precision, obs=obs)
            centroids = fit.centroids
        else:
            from map_oxidize_tpu.workloads.kmeans import KMeansMapper

            for it in range(start_iter, config.kmeans_iters):
                engine = make_engine(config, SumReducer(),
                                     value_shape=(d + 1,),
                                     value_dtype=np.float32)
                # the host assign (map_chunk) runs in the prefetch
                # thread, so assigning chunk i+1 overlaps chunk i's
                # engine feed + device dispatch
                mapper = KMeansMapper(centroids)
                mapped = pipelined(
                    (mapper.map_chunk(c) for c in
                     iter_point_chunks(config.input_path, rows)),
                    obs.knob("pipeline_depth", config.pipeline_depth),
                    obs, name="kmeans/map")
                centroids = kmeans_iteration(
                    engine, centroids, (), mapper=mapper, mapped=mapped)
                if want_iter_cb:
                    _iter_done(it + 1 - start_iter,
                               centroids if store else None)
    with obs.phase("write"):
        if config.output_path:
            from map_oxidize_tpu.workloads.kmeans import write_centroids

            write_centroids(config.output_path, centroids)
    ran_iters = max(config.kmeans_iters - start_iter, 0)
    if store:
        # a zero-work run (the snapshot already covered every requested
        # iteration) is a read of the continue-training state, not a
        # completion of it — deleting the snapshot then would destroy
        # training progress the run merely inspected
        store.finish(config.keep_intermediates or ran_iters == 0)
    # metrics reflect work THIS process performed: a resume that replayed a
    # snapshot ran only the remaining iterations, so throughput numerators
    # (records_in) must not count skipped ones.  `iters` is the number of
    # iterations the returned centroids represent.
    metrics.set("records_in", int(n) * ran_iters)
    metrics.set("points", int(n))
    metrics.set("dim", int(d))
    metrics.set("iters", start_iter + ran_iters)
    if start_iter:
        metrics.set("resumed_iters", start_iter)
    summary, trace = obs.finish(config, "kmeans")
    result = KMeansResult(centroids=centroids, metrics=summary, trace=trace)
    if fit is not None:
        result.labels, result.assigned_from = fit.labels, fit.assigned_from
    if config.metrics:
        _log.info("metrics: %s", result.metrics)
    return result


@dataclass
class DistinctResult:
    """HyperLogLog estimate plus the register state and per-phase metrics.
    ``registers`` is the dense ``(2^p,)`` int32 array (mergeable: max with
    another run's registers to estimate the union's cardinality)."""

    estimate: float
    registers: np.ndarray
    metrics: dict = field(default_factory=dict)
    trace: list | None = None

    def top_report(self, k: int) -> str:  # CLI-facing summary
        filled = int(np.count_nonzero(self.registers))
        return (f"distinct tokens ~ {self.estimate:,.0f}  "
                f"(HLL p={int(np.log2(self.registers.shape[0]))}, "
                f"{filled}/{self.registers.shape[0]} registers filled, "
                f"rse ~{104 / np.sqrt(self.registers.shape[0]):.2f}%)")


def run_distinct_job(config: JobConfig, on_obs=None) -> DistinctResult:
    """Approximate distinct-token count (HyperLogLog): max-monoid fold over
    ``2^p`` integer-keyed registers — the most engine-friendly reduce shape
    there is (fixed tiny key space, no dictionary, no growth), shared
    between the single-chip fold and the sharded mesh engine unchanged.
    See :mod:`map_oxidize_tpu.workloads.distinct` for the formulation."""
    return _recorded(config, "distinct", on_obs, _run_distinct_body)


def _run_distinct_body(config: JobConfig, obs: Obs) -> DistinctResult:
    from map_oxidize_tpu import runtime as _rt
    from map_oxidize_tpu.api import MaxReducer
    from map_oxidize_tpu.workloads.distinct import (
        DistinctMapper,
        hll_estimate,
    )

    p = config.hll_precision
    m = 1 << p
    use_native = _rt.resolve_mapper(config, "distinct") == "native"
    mapper = DistinctMapper(config.tokenizer, use_native, p)
    # Single-shard route: fold the (bucket, max-rank) rows straight into a
    # dense host register array — 2^p int32 is ~64KB at p=14, so each
    # chunk's fold is microseconds, while a device accumulator costs a
    # dispatch per chunk plus a finalize readback.  The sharded engine
    # keeps the device fold: it exists to prove the mesh path, and the
    # 1-vs-8-shard register-identity test pins both routes to the same
    # answer.
    engine = None
    host_regs = np.zeros(m, np.int32)
    if effective_num_shards(config) > 1:
        engine = make_engine(config, MaxReducer(), value_shape=(),
                             value_dtype=np.int32)
        engine.obs = obs
        engine.hint_total_keys(m)

    def _ingest(out) -> None:
        with obs.feed_span(rows=len(out)):
            if engine is not None:
                engine.feed(out)
            else:
                # lo is flatnonzero output — unique per chunk, so
                # fancy-index max is exact (and ~10x ufunc.at)
                idx = out.lo.astype(np.int64)
                host_regs[idx] = np.maximum(host_regs[idx], out.values)

    # registers are ordinary (key, value) rows, so the standard
    # per-chunk spill and replay apply
    stream = InputStream(config, obs, mapper, "distinct",
                         extra={"hll_precision": p})
    stream.run(_ingest)

    with obs.phase("finalize"):
        if engine is not None:
            hi, lo, vals, _n = engine.finalize()
            hi = np.asarray(hi)
            # device engines pad w/ SENTINEL
            live = hi != np.uint32(0xFFFFFFFF)
            regs = np.zeros(m, np.int32)
            regs[np.asarray(lo)[live].astype(np.int64)] = (
                np.asarray(vals)[live])
        else:
            regs = host_regs
        estimate = hll_estimate(regs)

    from map_oxidize_tpu.workloads.distinct import write_distinct_output

    return _finish_job(
        config, obs, "distinct", stream,
        lambda path: write_distinct_output(path, regs, estimate, p),
        DistinctResult(estimate=estimate, registers=regs),
        {"registers_filled": int(np.count_nonzero(regs))})

"""Dataflow workload drivers: total-order sort, hash equi-join,
sessionize (ROADMAP item 1 — the workloads that turn "word count,
generalized" into a general dataflow engine).

All three ride the pair-collect machinery (:mod:`runtime.collect`,
:mod:`parallel.collect`) — the one engine family whose rows SURVIVE the
reduce — from three new angles:

* **sort** routes with a sampled RANGE partition instead of the hash
  partition (``splitters=``), so per-shard sorted runs concatenate into
  the global total order; a beyond-RAM sort demotes to the PR-10 disk
  buckets, whose top-bit ranges make the bucket drain itself the merge.
* **join** feeds TWO corpora into one hash partition with the side
  tagged in the payload's top bit; the engine's (key, doc) sort leaves
  every key segment build-rows-then-probe-rows, and the probe is one
  vectorized CSR cross-product.
* **sessionize** feeds (key, timestamp) events; the same sort leaves
  each key's segment time-ascending, and one vectorized gap scan cuts
  sessions.

Attribution contract (the ``obs where`` ledger): the sample phase counts
as host produce, device finalize waits land in ``device_compute``, and
all host-side finalize compute (lexsorts, the probe expansion, session
cuts, ordered drain writes) is measured into the ``host_sort`` bucket —
minus any spill I/O paid inside the window, which ``spill_io`` owns —
so a sort job's wall stays >= 90% attributed instead of dumping its
finalize into ``unattributed_pct``.
"""

from __future__ import annotations

import time
from contextlib import contextmanager
from dataclasses import dataclass, field

import numpy as np

from map_oxidize_tpu.api import MapOutput
from map_oxidize_tpu.config import JobConfig
from map_oxidize_tpu.obs import Obs
from map_oxidize_tpu.runtime.pipeline import pipelined
from map_oxidize_tpu.utils.logging import get_logger

_log = get_logger(__name__)


def _overlay_compile_ms(obs) -> float:
    """Compile wall the observatory has attributed to this job so far
    (the live compile-ledger overlay) — what the device-wait window must
    subtract, because jit compiles synchronously inside the timed call
    and the ``compile`` bucket already owns that wall."""
    from map_oxidize_tpu.obs.compile import job_overlay_delta

    try:
        compile_ms = sum(float(r.get("compile_ms") or 0.0)
                         for r in job_overlay_delta(obs).values())
    except Exception:
        compile_ms = 0.0
    # the observatory's own cost-analysis lowering wall is paid inside
    # the compiling call too, and the compile bucket counts it
    return compile_ms + float(
        obs.registry.counters.get("attrib/lowering_ms", 0.0))


def _hist_total(obs, name: str) -> float:
    from map_oxidize_tpu.obs.attrib import _hist_total_ms

    return _hist_total_ms(obs.registry, name)


@contextmanager
def device_wait_window(obs):
    """Measure one device-synchronous finalize (dispatch + execute +
    fetch of the per-shard sort chain) into the ``device_compute``
    attribution bucket, MINUS whatever the observatory already recorded
    inside the window — compiling-call walls (the ``compile`` bucket
    owns them), dispatch gaps, and the SAMPLED ready-waits the xprof
    cadence takes on the very dispatches this window wraps (the first
    dispatch of a fresh program is always sampled) — so the buckets
    stay disjoint and their sum can never exceed the wall."""
    if obs is None:
        yield
        return
    c0 = _overlay_compile_ms(obs)
    g0 = _hist_total(obs, "device/dispatch_gap_ms")
    w0 = _hist_total(obs, "device/compute_ms")
    io0 = float(obs.registry.counters.get("spill/io_ms", 0.0))
    t0 = time.perf_counter()
    try:
        yield
    finally:
        dt_ms = (time.perf_counter() - t0) * 1e3
        io_ms = float(obs.registry.counters.get("spill/io_ms", 0.0)) - io0
        wait = max(dt_ms - (_overlay_compile_ms(obs) - c0)
                   - (_hist_total(obs, "device/dispatch_gap_ms") - g0)
                   - (_hist_total(obs, "device/compute_ms") - w0)
                   - io_ms, 0.0)
        obs.registry.observe("device/compute_ms", wait)


@contextmanager
def host_sort_window(obs):
    """Measure one host-side dataflow-finalize window (sort / probe /
    session cuts / ordered drain writes) into the attribution ledger's
    ``host_sort`` bucket.  Spill I/O paid INSIDE the window is
    subtracted — the ``spill_io`` bucket owns it, and attribution
    buckets must stay disjoint."""
    reg = obs.registry if obs is not None else None
    if reg is None:
        yield
        return
    io0 = float(reg.counters.get("spill/io_ms", 0.0))
    t0 = time.perf_counter()
    try:
        yield
    finally:
        dt_ms = (time.perf_counter() - t0) * 1e3
        io_ms = float(reg.counters.get("spill/io_ms", 0.0)) - io0
        reg.count("attrib/host_sort_ms", max(dt_ms - io_ms, 0.0))


def _make_engine(config: JobConfig, splitters=None):
    """The dataflow engines: the pair-collect family with the full
    unsigned (key, doc) lexsort discipline (``pair_order='lex'`` —
    payload order is part of these workloads' oracle), range-partitioned
    when ``splitters`` pin one (the sort), hash-partitioned otherwise
    (join/sessionize need co-location, not order)."""
    from map_oxidize_tpu.runtime.driver import (
        collect_engine_kw,
        effective_num_shards,
    )

    if effective_num_shards(config) > 1:
        from map_oxidize_tpu.parallel.collect import ShardedCollectEngine

        return ShardedCollectEngine(config, splitters=splitters,
                                    pair_order="lex",
                                    **collect_engine_kw(config))
    from map_oxidize_tpu.runtime.collect import CollectEngine

    return CollectEngine(config, pair_order="lex",
                         **collect_engine_kw(config))


def _feed_records(config: JobConfig, obs: Obs, engine, corpora) -> tuple:
    """Stream record chunks from ``corpora`` (``(path, doc_fn)`` pairs;
    ``doc_fn(payloads, path) -> i64 doc column``) through the engine
    under the pipeline wrapper.  Returns ``(records, n_chunks)``."""
    from map_oxidize_tpu.workloads.sort import iter_record_chunks

    records = 0
    n_chunks = 0
    rows_per_chunk = max(1, config.chunk_bytes // 16)

    def _gen():
        # heartbeat offsets accumulate ACROSS corpora (the join feeds
        # two): per-file offsets restart at 0 and the heartbeat's
        # monotone-max would discard the whole second corpus's progress
        base = 0
        for path, doc_fn in corpora:
            end = 0
            for k, p, end in iter_record_chunks(path, rows_per_chunk):
                out = MapOutput(hi=None, lo=None, values=None,
                                records_in=int(k.shape[0]), keys64=k,
                                docs64=doc_fn(p, path))
                yield out, base + end * 16
            base += end * 16

    for out, next_off in pipelined(_gen(),
                                   obs.knob("pipeline_depth",
                                            config.pipeline_depth),
                                   obs, name="map"):
        records += out.records_in
        n_chunks += 1
        with obs.feed_span(rows=len(out)):
            engine.feed(out)
        if obs.heartbeat is not None:
            obs.heartbeat.update(rows=out.records_in, bytes_done=next_off)
    return records, n_chunks


def _finalize_grouped(config: JobConfig, obs: Obs, engine):
    """Grouped-CSR finalize shared by join and sessionize: the spilled
    engines hand their CSR directly; resident engines hand sorted rows,
    boundary-detected here.  Device waits land in ``device_compute``,
    host sorts in ``host_sort``.  Returns ``(terms, offsets, docs,
    holder)`` (``holder`` keeps a spilled doc memmap alive)."""
    from map_oxidize_tpu.workloads.join import csr_from_sorted

    if getattr(engine, "spilled", False):
        with host_sort_window(obs):
            terms, offsets, docs, holder = engine.finalize_spilled_csr()
        return terms, offsets, docs, holder
    if hasattr(engine, "mesh"):
        # the fetch inside finalize blocks on the per-shard device sort
        # chain — consumer-visible device time, same contract as the
        # wordcount readback (compile/dispatch walls subtracted: their
        # buckets own them)
        with device_wait_window(obs):
            keys, docs = engine.finalize()
        with host_sort_window(obs):
            csr = csr_from_sorted(keys, docs)
    else:
        with host_sort_window(obs):
            keys, docs = engine.finalize()
            csr = csr_from_sorted(keys, docs)
    return (*csr, None)


# --- total-order sort ------------------------------------------------------


@dataclass
class SortResult:
    """Global facts of a total-order sort; the sorted artifact itself
    streams to ``config.output_path`` (16-byte ``OUT_REC`` records whose
    file concatenation, part-major, is globally sorted)."""

    n_rows: int
    n_shards: int
    splitters: "np.ndarray | None"
    spilled_rows: int = 0
    metrics: dict = field(default_factory=dict)
    trace: "list | None" = None

    def top_report(self, k: int) -> str:  # CLI-facing summary
        spill = (f", {self.spilled_rows} rows via disk buckets"
                 if self.spilled_rows else "")
        return (f"sort: {self.n_rows} rows total-ordered across "
                f"{self.n_shards} range(s){spill}")


def run_sort_job(config: JobConfig, on_obs=None) -> SortResult:
    """TeraSort-style total-order sort: sample -> range splitters ->
    ``all_to_all`` route -> per-shard ``lax.sort`` -> ordered writes.
    Beyond-RAM runs demote to the shuffle layer's disk buckets and the
    bucket drain preserves the total order (top-bit ranges + per-bucket
    lexsort)."""
    config.validate()
    obs = Obs.from_config(config)
    if on_obs is not None:
        on_obs(obs)
    with obs.recording(config, "sort"):
        return _run_sort_body(config, obs)


def _run_sort_body(config: JobConfig, obs: Obs) -> SortResult:
    from map_oxidize_tpu.runtime.driver import effective_num_shards
    from map_oxidize_tpu.workloads.sort import (
        compute_splitters,
        load_records,
        sample_keys,
        write_sorted_records,
    )

    metrics = obs.registry
    n_shards = effective_num_shards(config)
    with obs.phase("sample"):
        _keys, _payloads, n_total = load_records(config.input_path)
        splitters = None
        if n_shards > 1:
            splitters = compute_splitters(
                sample_keys(config.input_path, config.sort_sample),
                n_shards)
            metrics.set("sort/splitters", int(splitters.shape[0]))
    engine = _make_engine(config, splitters=splitters)
    engine.obs = obs
    metrics.set("shuffle/transport", engine.transport)

    with obs.phase("map+route"):
        records, n_chunks = _feed_records(
            config, obs, engine,
            [(config.input_path, lambda p, _path: p.view(np.int64))])

    rows_out = 0
    with obs.phase("merge"):
        if getattr(engine, "spilled", False):
            runs = engine.finalize_spilled_runs()
            with host_sort_window(obs):
                if config.output_path:
                    rows_out = write_sorted_records(config.output_path,
                                                    runs)
                else:
                    rows_out = sum(int(k.shape[0]) for k, _d in runs)
        else:
            if hasattr(engine, "mesh"):
                with device_wait_window(obs):
                    keys, docs = engine.finalize()
            else:
                with host_sort_window(obs):
                    keys, docs = engine.finalize()
            with host_sort_window(obs):
                if config.output_path:
                    rows_out = write_sorted_records(config.output_path,
                                                    [(keys, docs)])
                else:
                    rows_out = int(keys.shape[0])

    # row conservation: a sort loses or invents nothing
    if rows_out != records or records != n_total:
        raise RuntimeError(
            f"sort row conservation violated: {n_total} input rows, "
            f"{records} fed, {rows_out} out")
    metrics.set("records_in", records)
    metrics.set("rows_out", rows_out)
    metrics.set("chunks", n_chunks)
    metrics.set("device_rows_fed", engine.rows_fed)
    spilled = int(getattr(engine, "spilled_rows", 0))
    summary, trace = obs.finish(config, "sort")
    result = SortResult(n_rows=rows_out, n_shards=n_shards,
                        splitters=splitters, spilled_rows=spilled,
                        metrics=summary, trace=trace)
    if config.metrics:
        _log.info("metrics: %s", result.metrics)
    return result


# --- hash equi-join --------------------------------------------------------


@dataclass
class JoinResult:
    """Global facts of a hash equi-join; matches stream to
    ``config.output_path`` as 24-byte ``JOIN_REC`` records, lexsorted by
    (key, left payload, right payload)."""

    n_matches: int
    n_left: int
    n_right: int
    n_keys: int
    metrics: dict = field(default_factory=dict)
    trace: "list | None" = None

    def top_report(self, k: int) -> str:
        return (f"join: {self.n_matches} matches from {self.n_left} x "
                f"{self.n_right} rows ({self.n_keys} distinct keys)")


def run_join_job(config: JobConfig, on_obs=None) -> JoinResult:
    """Hash equi-join of ``config.input_path`` (left/build) with
    ``config.join_input_path`` (right/probe) on the record key: both
    corpora co-partition through one pair-collect engine, each key
    segment comes out build-rows-then-probe-rows, and the probe is one
    vectorized cross-product expansion."""
    config.validate()
    if not config.join_input_path:
        raise ValueError(
            "join needs the right-side corpus: --join-input "
            "(config.join_input_path)")
    obs = Obs.from_config(config)
    if on_obs is not None:
        on_obs(obs)
    with obs.recording(config, "join"):
        return _run_join_body(config, obs)


def _run_join_body(config: JobConfig, obs: Obs) -> JoinResult:
    from map_oxidize_tpu.workloads.join import (
        check_join_payloads,
        lexsort_matches,
        probe_join_csr,
        tag_side,
        write_join_records,
    )

    metrics = obs.registry
    engine = _make_engine(config)
    engine.obs = obs
    metrics.set("shuffle/transport", engine.transport)

    sides = {}

    def _doc_fn(right):
        def fn(p, path):
            check_join_payloads(p, path)
            sides[right] = sides.get(right, 0) + int(p.shape[0])
            return tag_side(p, right).view(np.int64)
        return fn

    with obs.phase("map+route"):
        records, n_chunks = _feed_records(
            config, obs, engine,
            [(config.input_path, _doc_fn(False)),
             (config.join_input_path, _doc_fn(True))])

    with obs.phase("merge"):
        terms, offsets, docs, holder = _finalize_grouped(config, obs,
                                                         engine)
        with host_sort_window(obs):
            mk, ma, mb = probe_join_csr(terms, offsets, docs)
            mk, ma, mb = lexsort_matches(mk, ma, mb)
        del holder  # probe consumed the doc column

    with obs.phase("write"):
        if config.output_path:
            write_join_records(config.output_path, mk, ma, mb)

    metrics.set("records_in", records)
    metrics.set("chunks", n_chunks)
    metrics.set("join/matches", int(mk.shape[0]))
    metrics.set("join/left_rows", sides.get(False, 0))
    metrics.set("join/right_rows", sides.get(True, 0))
    metrics.set("distinct_keys", int(terms.shape[0]))
    summary, trace = obs.finish(config, "join")
    result = JoinResult(n_matches=int(mk.shape[0]),
                        n_left=sides.get(False, 0),
                        n_right=sides.get(True, 0),
                        n_keys=int(terms.shape[0]),
                        metrics=summary, trace=trace)
    if config.metrics:
        _log.info("metrics: %s", result.metrics)
    return result


# --- sessionize ------------------------------------------------------------


@dataclass
class SessionizeResult:
    """Global facts of a sessionize run; sessions stream to
    ``config.output_path`` as ``key<TAB>start<TAB>end<TAB>count`` lines
    sorted by (key, start)."""

    n_sessions: int
    n_events: int
    n_keys: int
    metrics: dict = field(default_factory=dict)
    trace: "list | None" = None

    def top_report(self, k: int) -> str:
        return (f"sessionize: {self.n_sessions} sessions from "
                f"{self.n_events} events ({self.n_keys} keys)")


def run_sessionize_job(config: JobConfig, on_obs=None) -> SessionizeResult:
    """Gap-cut sessionization of (key, timestamp) events: hash-group by
    key, time-order each key's events through the engine's (key, ts)
    sort, cut sessions wherever the gap exceeds
    ``config.session_gap``."""
    config.validate()
    obs = Obs.from_config(config)
    if on_obs is not None:
        on_obs(obs)
    with obs.recording(config, "sessionize"):
        return _run_sessionize_body(config, obs)


def _run_sessionize_body(config: JobConfig, obs: Obs) -> SessionizeResult:
    from map_oxidize_tpu.workloads.sessionize import (
        sessions_from_csr,
        sort_sessions,
        write_sessions,
    )

    metrics = obs.registry
    engine = _make_engine(config)
    engine.obs = obs
    metrics.set("shuffle/transport", engine.transport)

    with obs.phase("map+route"):
        records, n_chunks = _feed_records(
            config, obs, engine,
            [(config.input_path, lambda p, _path: p.view(np.int64))])

    with obs.phase("merge"):
        terms, offsets, docs, holder = _finalize_grouped(config, obs,
                                                         engine)
        with host_sort_window(obs):
            sk, ss, se, sc = sessions_from_csr(terms, offsets, docs,
                                               config.session_gap)
            sk, ss, se, sc = sort_sessions(sk, ss, se, sc)
        del holder

    # event conservation: every event lands in exactly one session
    if int(sc.sum()) != records:
        raise RuntimeError(
            f"sessionize event conservation violated: {records} events "
            f"fed, sessions cover {int(sc.sum())}")

    with obs.phase("write"):
        if config.output_path:
            write_sessions(config.output_path, sk, ss, se, sc)

    metrics.set("records_in", records)
    metrics.set("chunks", n_chunks)
    metrics.set("sessions/count", int(sk.shape[0]))
    metrics.set("distinct_keys", int(terms.shape[0]))
    summary, trace = obs.finish(config, "sessionize")
    result = SessionizeResult(n_sessions=int(sk.shape[0]),
                              n_events=records,
                              n_keys=int(terms.shape[0]),
                              metrics=summary, trace=trace)
    if config.metrics:
        _log.info("metrics: %s", result.metrics)
    return result

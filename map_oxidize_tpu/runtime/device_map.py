"""Device-map job driver: the whole map+reduce on the TPU.

The host's role in this path is reduced to what only it can do: stream file
bytes, ship them to HBM, and keep the hash -> token-bytes dictionary (sliced
from raw chunk bytes at device-reported representative offsets).  Tokenize,
hash, combine, and the streaming reduce all happen on device
(:mod:`map_oxidize_tpu.ops.device_tokenize` + the accumulator merge), so
throughput is bounded by the host->device link and chip compute, not the
host CPU — the reference runs this entire phase on host threads
(``/root/reference/src/main.rs:53-101``).

Pipelining: chunk N+1's upload + kernel are dispatched (async) *before*
chunk N's small dictionary readback blocks, so the fixed fetch latency
hides behind compute.
"""

from __future__ import annotations

from functools import lru_cache

import numpy as np
import jax
import jax.numpy as jnp

from map_oxidize_tpu.api import SumReducer
from map_oxidize_tpu.config import JobConfig
from map_oxidize_tpu.io.splitter import iter_chunks_capped
from map_oxidize_tpu.ops.device_tokenize import (
    DeviceTokenizer,
    ngram_at,
    pad_chunk,
)
from map_oxidize_tpu.ops.hashing import HashDictionary
from map_oxidize_tpu.runtime.driver import JobResult, _readback, write_counts
from map_oxidize_tpu.runtime.engine import (
    CapacityError,
    DeviceReduceEngine,
    next_pow2,
)
from map_oxidize_tpu.utils.jax_compat import shard_map
from map_oxidize_tpu.obs import Obs
from map_oxidize_tpu.utils.logging import get_logger

_log = get_logger(__name__)


@lru_cache(maxsize=None)
def _prefix_packer(m: int):
    """[3, m] uint32 overflow fetch, used only when per-chunk novelty
    exceeds the kernel's pre-packed ``fetch_keys`` rows."""
    from map_oxidize_tpu.obs.compile import observed_jit

    def pack(hi, lo, reps):
        return jnp.stack([hi[:m], lo[:m], reps[:m].astype(jnp.uint32)])
    return observed_jit("device_map/prefix_pack", jax.jit(pack), tag=m)


class _DictBuilder:
    """Builds the hash -> token-bytes dictionary from device outputs.

    The kernel pre-packs (scalars + first ``fetch_keys`` dictionary rows)
    into one array, so the steady-state cost here is a single host fetch per
    chunk — fetch latency is the remote-device tax, so one is the budget.
    """

    def __init__(self, out_keys: int, fetch_keys: int, ngram: int = 1):
        self.dictionary = HashDictionary()
        self.out_keys = out_keys
        self.fetch_keys = min(fetch_keys, out_keys)
        self.records_in = 0
        self.ngram = ngram

    def process(self, chunk: bytes, outs) -> None:
        u_hi, u_lo, counts, reps, packed_dev = outs
        packed = np.asarray(packed_dev)  # THE one blocking fetch per chunk
        self.process_packed(
            chunk, packed,
            lambda nu: self._fetch_overflow(u_hi, u_lo, reps, nu))

    def _fetch_overflow(self, u_hi, u_lo, reps, nu: int):
        """Rare path: per-chunk novelty exceeded the pre-packed window, so
        the full (hi, lo, rep) prefix must be fetched separately."""
        m = min(next_pow2(nu), self.out_keys)
        over = np.asarray(_prefix_packer(m)(u_hi, u_lo, reps))
        return over[0][:nu], over[1][:nu], over[2][:nu]

    def process_packed(self, chunk: bytes, packed: np.ndarray,
                       fetch_overflow) -> None:
        """Update the dictionary from one already-fetched packed row (the
        sharded path fetches a whole group's [S, ...] packed array at once
        and calls this per shard)."""
        nu, ndrop, ntok = packed[:3].astype(np.int64).tolist()
        if ndrop:
            raise CapacityError(
                f"{ndrop} unique keys dropped in a chunk: raise "
                "device_chunk_keys above the per-chunk distinct-key count"
            )
        self.records_in += ntok
        if nu == 0:
            return
        f = self.fetch_keys
        if nu <= f:
            hi, lo, rep = (packed[3:3 + nu],
                           packed[3 + f:3 + f + nu],
                           packed[3 + 2 * f:3 + 2 * f + nu])
        else:  # rare: more novelty than the pre-packed window
            hi, lo, rep = fetch_overflow(nu)
        h64 = ((hi.astype(np.uint64) << np.uint64(32))
               | lo.astype(np.uint64)).tolist()
        d = self.dictionary
        rl = rep.astype(np.int64).tolist()
        ng = self.ngram
        for i, h in enumerate(h64):
            # unconditional add: on a repeat hash this compares the stored
            # bytes against this chunk's representative token, so a 64-bit
            # device-hash collision (two tokens, one hash) raises here just
            # as it would on the host paths instead of silently merging
            d.add(h, ngram_at(chunk, rl[i], ng))


def run_sharded_device_job(config: JobConfig, ngram: int = 1,
                           on_obs=None) -> JobResult:
    """Word/n-gram count with the map phase on device across a mesh.

    Chunks are dealt round-robin onto shards in groups of S; one
    ``device_put`` ships the group as a ``[S * chunk_bytes]`` byte array
    sharded over the mesh, a ``shard_map`` runs the fused tokenize kernel
    per shard, and the per-shard unique rows flow straight into the
    ``all_to_all`` exchange via the sharded engine's ``feed_device`` — the
    map->shuffle hand-off never touches the host.  The host's only
    steady-state work is streaming file bytes and the one packed dictionary
    fetch per group (pipelined one group behind, so it overlaps compute).
    """
    config.validate()
    obs = Obs.from_config(config)
    if on_obs is not None:
        on_obs(obs)
    with obs.recording(config, "bigram" if ngram == 2 else "wordcount"):
        return _run_sharded_device_body(config, obs, ngram)


def _run_sharded_device_body(config: JobConfig, obs, ngram: int) -> JobResult:
    from jax.sharding import NamedSharding, PartitionSpec as P

    from dataclasses import replace

    from map_oxidize_tpu.ops.device_tokenize import (
        _power_tables,
        tokenize_count_core,
    )
    from map_oxidize_tpu.parallel.engine import ShardedReduceEngine
    from map_oxidize_tpu.parallel.mesh import SHARD_AXIS

    metrics = obs.registry
    N = config.chunk_bytes
    max_tokens = N // 2 + 1
    out_keys = min(config.device_chunk_keys, max_tokens)  # kernel clamps
    fetch = min(1 << 16, out_keys)
    # build the mesh first so S is known: the engine's merge batch is one
    # tokenized group (S shards x out_keys rows), so its bucket_cap and
    # feed_batch must be sized for that, not for config.batch_size
    from map_oxidize_tpu.parallel.mesh import make_mesh

    mesh = make_mesh(config.num_shards, config.backend)
    S = mesh.shape[SHARD_AXIS]
    engine = ShardedReduceEngine(
        replace(config, batch_size=S * out_keys), SumReducer(), mesh=mesh)
    engine.obs = obs
    pk = _power_tables(N)
    rep_spec = NamedSharding(mesh, P())
    row_spec = NamedSharding(mesh, P(SHARD_AXIS))
    tables = tuple(jax.device_put(t, rep_spec) for t in pk)

    from map_oxidize_tpu.obs.compile import observed_jit

    group_fn = observed_jit("device_map/tokenize_group", jax.jit(shard_map(
        lambda chunk, a, b, c, d: tokenize_count_core(
            chunk, a, b, c, d, max_tokens=max_tokens, out_keys=out_keys,
            fetch_keys=fetch, ngram=ngram),
        mesh=mesh,
        in_specs=(P(SHARD_AXIS), P(), P(), P(), P()),
        out_specs=P(SHARD_AXIS),
    )), tag=(S, out_keys, ngram))

    dicts = [_DictBuilder(out_keys, fetch, ngram) for _ in range(S)]
    pending: tuple | None = None
    n_chunks = 0

    ckpt = _open_snapshot(config, f"device-map-sharded-ngram{ngram}", S,
                          registry=metrics)

    def _set_dict(d, records):
        # the snapshot stores the UNION dictionary; shard 0 carries it on
        # resume (finalize unions the builders anyway)
        dicts[0].dictionary = d
        dicts[0].records_in = records

    resume_off, n_chunks = _resume_snapshot(ckpt, engine, _set_dict)

    def _process_group(chunks: list[bytes], outs) -> None:
        u_hi, u_lo, reps, packed_dev = outs
        packed = np.asarray(packed_dev).reshape(S, -1)  # ONE fetch per group
        for s, chunk in enumerate(chunks):
            dicts[s].process_packed(
                chunk, packed[s],
                lambda nu, s=s: dicts[s]._fetch_overflow(
                    u_hi[s * out_keys:(s + 1) * out_keys],
                    u_lo[s * out_keys:(s + 1) * out_keys],
                    reps[s * out_keys:(s + 1) * out_keys], nu))

    def _snapshot(off: int) -> None:
        union = HashDictionary()
        for d in dicts:
            union.update(d.dictionary)
        ckpt.save_snapshot(
            engine.export_state(), union, off, n_chunks,
            {"records_in": np.int64(sum(d.records_in for d in dicts))})

    with obs.phase("map+reduce"):
        group: list[bytes] = []
        off = resume_off
        groups_done = 0
        hb_records = sum(d.records_in for d in dicts)
        for chunk in iter_chunks_capped(config.input_path, config.chunk_bytes,
                                        resume_off):
            group.append(bytes(chunk))
            n_chunks += 1
            off += len(chunk)
            if obs.heartbeat is not None:
                # rows = tokenized-record delta (one group behind — the
                # dictionary fetch is pipelined); bytes drive the percent
                total = sum(d.records_in for d in dicts)
                obs.heartbeat.update(rows=total - hb_records,
                                     bytes_done=off)
                hb_records = total
            if len(group) < S:
                continue
            pending = _dispatch_group(group, group_fn, N, tables, engine,
                                      row_spec, pending, _process_group)
            group = []
            groups_done += 1
            engine.hint_live_upper_bound(
                sum(len(d.dictionary) for d in dicts) + 2 * S * out_keys)
            if ckpt is not None and groups_done % _SNAP_EVERY == 0:
                if pending is not None:
                    _process_group(*pending)  # sync dictionaries
                    pending = None
                _snapshot(off)
        if group:  # short tail group: pad with empty (all-space) chunks
            group += [b""] * (S - len(group))
            pending = _dispatch_group(group, group_fn, N, tables, engine,
                                      row_spec, pending, _process_group)
        if pending is not None:
            _process_group(*pending)
        if obs.heartbeat is not None:  # tail records the pipeline lagged
            obs.heartbeat.update(
                rows=sum(d.records_in for d in dicts) - hb_records)

    with obs.phase("finalize"):
        dictionary = dicts[0].dictionary
        for d in dicts[1:]:
            dictionary.update(d.dictionary)
        counts = _readback(engine, dictionary)
        top = counts.top_k(config.top_k)

    records_in = sum(d.records_in for d in dicts)
    total = counts.total()
    if records_in and total != records_in:
        raise RuntimeError(
            f"count conservation violated: device tokenized "
            f"{records_in} records but counts sum to {total}"
        )

    with obs.phase("write"):
        if config.output_path:
            write_counts(obs, config.output_path, counts)

    if ckpt is not None:
        ckpt.finish(config.keep_intermediates)

    metrics.set("records_in", records_in)
    metrics.set("distinct_keys", len(counts))
    metrics.set("chunks", n_chunks)
    metrics.set("shards", S)
    summary, trace = obs.finish(config,
                                "bigram" if ngram == 2 else "wordcount")
    result = JobResult(counts=counts, top=top, metrics=summary, trace=trace)
    if config.metrics:
        _log.info("metrics: %s", result.metrics)
    return result


def _dispatch_group(group, group_fn, chunk_bytes, tables, engine, row_spec,
                    pending, process):
    """Upload one S-chunk group, run the sharded tokenize, feed the engine
    (all async), then block on the PREVIOUS group's dictionary fetch so it
    overlaps this group's compute."""
    stacked = np.concatenate([pad_chunk(c, chunk_bytes) for c in group])
    dev = jax.device_put(stacked, row_spec)
    u_hi, u_lo, cnts, reps, packed = group_fn(dev, *tables)
    engine.feed_device(u_hi, u_lo, cnts)
    if pending is not None:
        process(*pending)
    return (group, (u_hi, u_lo, reps, packed))


#: snapshot cadence for the device-map checkpoint (chunks between engine
#: state spills); each snapshot serializes the pipeline for one dictionary
#: fetch, so the cadence trades resume granularity against overlap
_SNAP_EVERY = 16


def _open_snapshot(config: JobConfig, workload_tag: str, num_shards: int,
                   registry=None):
    """Device-map checkpointing: map outputs never exist on the host here,
    so the resumable artifact is a periodic SNAPSHOT of the reduced state
    (engine accumulator + dictionary + input byte offset) rather than the
    host paths' per-chunk spill.  The mesh shape is part of the identity:
    an S-shard engine state cannot be restored onto a different mesh (the
    hash partition is baked into the row layout), so a shard-count change
    discards the snapshot and re-maps from scratch."""
    if not config.checkpoint_dir:
        return None
    from map_oxidize_tpu.runtime.checkpoint import CheckpointStore

    return CheckpointStore(
        config.checkpoint_dir,
        CheckpointStore.job_meta(
            config, workload_tag,
            extra={"num_shards": num_shards,
                   "device_chunk_keys": config.device_chunk_keys}),
        registry=registry)


def _resume_snapshot(ckpt, engine, set_dictionary) -> tuple[int, int]:
    """Shared snapshot-restore: import engine state, hand the union
    dictionary + prior records_in to ``set_dictionary``, return
    ``(resume_offset, n_chunks)`` (0, 0 when there is nothing to resume)."""
    if ckpt is None:
        return 0, 0
    snap = ckpt.load_snapshot()
    if snap is None:
        return 0, 0
    state, d, resume_off, n_chunks, extra = snap
    engine.import_state(state)
    set_dictionary(d, int(extra["records_in"]))
    _log.info("resumed device-map snapshot: %d chunks, offset %d",
              n_chunks, resume_off)
    return resume_off, n_chunks


def run_device_wordcount_job(config: JobConfig, ngram: int = 1,
                             on_obs=None) -> JobResult:
    """Word/n-gram count with the map phase on device (single chip)."""
    config.validate()
    obs = Obs.from_config(config)
    if on_obs is not None:
        on_obs(obs)
    with obs.recording(config, "bigram" if ngram == 2 else "wordcount"):
        return _run_device_wordcount_body(config, obs, ngram)


def _run_device_wordcount_body(config: JobConfig, obs,
                               ngram: int) -> JobResult:
    metrics = obs.registry
    engine = DeviceReduceEngine(config, SumReducer())
    engine.obs = obs
    tok = DeviceTokenizer(config.chunk_bytes, config.device_chunk_keys,
                          device=engine.device, ngram=ngram)
    dicts = _DictBuilder(tok.out_keys, tok.fetch_keys, ngram)

    ckpt = _open_snapshot(config, f"device-map-ngram{ngram}", 1,
                          registry=metrics)

    def _set_dict(d, records):
        dicts.dictionary = d
        dicts.records_in = records
        engine.hint_live_upper_bound(len(d))

    resume_off, n_chunks = _resume_snapshot(ckpt, engine, _set_dict)

    pending: tuple | None = None
    off = resume_off
    hb_records = dicts.records_in
    with obs.phase("map+reduce"):
        for chunk in iter_chunks_capped(config.input_path, config.chunk_bytes,
                                        resume_off):
            outs = tok.map_chunk_device(chunk)          # async upload + kernel
            engine.feed_device(outs[0], outs[1], outs[2])  # async merge
            if pending is not None:
                dicts.process(*pending)   # blocks; overlaps current compute
            pending = (chunk, outs)
            n_chunks += 1
            off += len(chunk)
            if obs.heartbeat is not None:
                # rows = tokenized-record delta (one chunk behind — the
                # dictionary fetch is pipelined); bytes drive the percent
                obs.heartbeat.update(rows=dicts.records_in - hb_records,
                                     bytes_done=off)
                hb_records = dicts.records_in
            # the dictionary length is the exact global distinct-key count
            # (one chunk behind) — feed it back so capacity growth rarely
            # needs its own device sync
            engine.hint_live_upper_bound(
                len(dicts.dictionary) + config.device_chunk_keys)
            if ckpt is not None and n_chunks % _SNAP_EVERY == 0:
                dicts.process(*pending)  # sync the dictionary to the engine
                pending = None
                ckpt.save_snapshot(
                    engine.export_state(), dicts.dictionary, off, n_chunks,
                    {"records_in": np.int64(dicts.records_in)})
        if pending is not None:
            dicts.process(*pending)
        if obs.heartbeat is not None:  # tail records the pipeline lagged
            obs.heartbeat.update(rows=dicts.records_in - hb_records)

    with obs.phase("finalize"):
        counts = _readback(engine, dicts.dictionary)
        top = counts.top_k(config.top_k)

    total = counts.total()
    if dicts.records_in and total != dicts.records_in:
        raise RuntimeError(
            f"count conservation violated: device tokenized "
            f"{dicts.records_in} tokens but counts sum to {total}"
        )

    with obs.phase("write"):
        if config.output_path:
            write_counts(obs, config.output_path, counts)

    if ckpt is not None:
        ckpt.finish(config.keep_intermediates)

    metrics.set("records_in", dicts.records_in)
    metrics.set("distinct_keys", len(counts))
    metrics.set("chunks", n_chunks)
    summary, trace = obs.finish(config,
                                "bigram" if ngram == 2 else "wordcount")
    result = JobResult(counts=counts, top=top, metrics=summary, trace=trace)
    if config.metrics:
        _log.info("metrics: %s", result.metrics)
    return result

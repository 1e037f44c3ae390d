"""Input stream: how a host-mapped job's input becomes an ordered,
resumable stream of ``MapOutput``s, decided once.  For word count,
bigram, the inverted index and distinct it owns all of it: checkpoint
replay and save, split and map route, prefetch, and the heartbeat's
progress.  For each process of a distributed job it owns replay, save,
prefetch and progress; that job cuts and maps its own chunks
(``parallel/distributed.py`` ``_local_chunks``/``_map_local``).  See
``docs/DESIGN.md``, "Input stream"."""

from __future__ import annotations

import os
from functools import cached_property

from map_oxidize_tpu.io import splitter
from map_oxidize_tpu.runtime.executor import run_map_phase
from map_oxidize_tpu.runtime.pipeline import pipelined
from map_oxidize_tpu.utils.logging import get_logger

_log = get_logger(__name__)


def scan_workers(config) -> int:
    """Host cores the native mmap scan may fan out over: the configured
    map workers, capped by the CPUs this process may run on (the scan
    caps it again by the ranges left)."""
    try:
        cpus = len(os.sched_getaffinity(0))
    except AttributeError:  # no affinity call on this platform
        cpus = os.cpu_count() or 1
    return max(1, min(config.num_map_workers, cpus))


def _track_ends(chunks, off: int, ends: dict):
    """Pass byte-range chunks on, noting each one's end offset by index."""
    for i, mv in enumerate(chunks):
        off += len(mv)
        ends[i] = off
        yield mv


class InputStream:
    """One job's input: :meth:`run` (or :meth:`saved` then :meth:`saving`
    for a caller that maps its own chunks), then :meth:`finish` after
    the write.  ``records_in`` and ``chunks`` count every output the
    caller has taken, replayed ones included.  ``round_robin`` deals the
    input's lines into ``config.num_chunks`` chunks, the reference's
    split (whole file resident: for parity runs, not for scale)."""

    def __init__(self, config, obs, mapper, workload: str, *,
                 push: bool = False, round_robin: bool = False,
                 hash_only: bool = False, extra: dict | None = None,
                 subdir: str = ""):
        self.config, self.obs, self.mapper = config, obs, mapper
        self.push, self.round_robin = push, round_robin
        self.resume_k = self.resume_off = 0  # where mapping resumes
        self.records_in = self.chunks = 0
        self.ckpt = None
        if config.checkpoint_dir:
            from map_oxidize_tpu.runtime.checkpoint import CheckpointStore

            self.ckpt = CheckpointStore(
                os.path.join(config.checkpoint_dir, subdir),
                CheckpointStore.job_meta(config, workload,
                                         hash_only=hash_only, extra=extra),
                registry=obs.registry)

    @cached_property
    def chunk_bytes(self) -> int:
        """Byte-range cut size, shared with a hash-only rescan so that
        both cut the input alike; a round-robin split never reads it."""
        return splitter.plan_chunks(self.config.input_path,
                                    self.config.chunk_bytes)[1]

    def saved(self):
        """The saved prefix's outputs, lazily.  A corrupt chunk ends the
        prefix; the ranges after it map again."""
        for idx, out, end in self.ckpt.replay() if self.ckpt else ():
            self.resume_k, self.resume_off = idx + 1, max(end, 0)
            yield out
            self._progress(out, None)
        if self.resume_k:
            _log.info("resumed %d checkpointed chunks from %s (input offset "
                      "%d)", self.resume_k, self.ckpt.dir, self.resume_off)

    def run(self, ingest, phase: str = "map+reduce",
            split_phase: bool = True) -> None:
        """Hand every output to ``ingest``: the saved prefix under
        ``phase/replay``, then the fresh chunks under ``phase/<phase>``,
        split under ``phase/split`` first (inside ``phase`` without
        ``split_phase``)."""
        if self.ckpt is not None:
            with self.obs.phase("replay"):
                for out in self.saved():
                    ingest(out)
        if split_phase:
            with self.obs.phase("split"):
                self.split()
        with self.obs.phase(phase):
            if not split_phase:
                self.split()
            for out in self:
                ingest(out)

    def split(self) -> None:
        """Open the fresh chunks' source at the resume point.  Only the
        native scan starts work here: its workers run ahead."""
        cfg, mapper, self._ends, self._ordered = (
            self.config, self.mapper, {}, None)
        if self.round_robin:  # a chunk's identity: its index
            self._chunks = splitter.split_round_robin(
                cfg.input_path, cfg.num_chunks)[self.resume_k:]
            return
        if hasattr(mapper, "map_file"):
            self._ordered = mapper.map_file(cfg.input_path, self.chunk_bytes,
                                            self.resume_off,
                                            scan_workers(cfg))
        if self._ordered is None and hasattr(mapper, "map_docs"):
            self._ordered = self._doc_chunks()
        elif self._ordered is None:
            self._chunks = _track_ends(
                splitter.iter_chunks(cfg.input_path, self.chunk_bytes,
                                     self.resume_off),
                self.resume_off, self._ends)

    def _doc_chunks(self):
        off = self.resume_off
        for chunk in splitter.iter_doc_chunks(self.config.input_path,
                                              self.chunk_bytes, off):
            base, off = off, off + len(chunk)
            yield self.mapper.map_docs(chunk, base), off

    def _depth(self) -> int:
        # the push cadence needs a producer that actually runs ahead
        depth = self.obs.knob("pipeline_depth", self.config.pipeline_depth)
        return max(2, int(depth)) if self.push else depth

    def prefetch(self, items):
        """``items`` behind the job's prefetcher, named for its cadence."""
        return pipelined(items, self._depth(), self.obs,
                         name="push" if self.push else "map",
                         ratio_gauge=("pipeline/shuffle_overlap_ratio"
                                      if self.push else None))

    def __iter__(self):
        """The fresh outputs, in file order; the python pool route's come
        as their chunks finish, each saved under its own index."""
        if self._ordered is not None:
            return self.saving(enumerate(self.prefetch(self._ordered)))
        cfg = self.config
        pool = run_map_phase(self._chunks, self.mapper, cfg.num_map_workers,
                             cfg.max_retries, pipeline_depth=self._depth(),
                             obs=self.obs)
        return self.saving((i, (out, self._ends.pop(i, None)))
                           for i, out in pool)

    def saving(self, indexed):
        """Pass fresh ``(index, (MapOutput, end_offset))`` items on, each
        saved at ``resume_k + index`` once the caller has taken it."""
        for i, (out, end) in indexed:
            yield out
            self._progress(out, end)
            if self.ckpt is not None:
                self.ckpt.save(self.resume_k + i, out,
                               -1 if end is None else end)

    def _progress(self, out, end) -> None:
        """Count an output the caller has taken and beat: rows and end
        offset in ONE update, so no percent lags the rows by a chunk."""
        self.records_in += out.records_in
        self.chunks += 1
        if self.obs.heartbeat is not None:
            self.obs.heartbeat.update(rows=out.records_in, bytes_done=end)

    def finish(self) -> None:
        if self.ckpt is not None:
            self.ckpt.finish(self.config.keep_intermediates)

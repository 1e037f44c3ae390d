"""Checkpoint/resume: a job killed mid-map resumes without re-mapping the
spilled prefix and produces byte-identical output.

The reference's intermediate files (main.rs:74-75) could have supported this
but nothing reads them across runs; here resume is a tested contract
(VERDICT round 1, item 7)."""

import os
import threading

import numpy as np
import pytest

from map_oxidize_tpu.config import JobConfig
from map_oxidize_tpu.runtime import run_job
from map_oxidize_tpu.runtime.checkpoint import CheckpointStore
from map_oxidize_tpu.workloads.wordcount import WordCountMapper


def _make_corpus(path, n_lines=4000, seed=0):
    rng = np.random.default_rng(seed)
    words = [b"alpha", b"beta", b"Gamma,", b"delta.", b"epsilon", b"zeta"]
    with open(path, "wb") as f:
        for _ in range(n_lines):
            k = int(rng.integers(3, 9))
            f.write(b" ".join(words[int(i)] for i in rng.integers(0, 6, k)))
            f.write(b"\n")


class _DyingMapper(WordCountMapper):
    """Aborts the run after ``die_after`` chunks — the mid-run kill."""

    def __init__(self, die_after: int, **kw):
        super().__init__(**kw)
        self.mapped = 0
        self.die_after = die_after

    def map_chunk(self, chunk):
        if self.mapped >= self.die_after:
            raise KeyboardInterrupt("simulated kill")
        self.mapped += 1
        return super().map_chunk(chunk)


def _cfg(corpus, out, ckdir, **kw):
    base = dict(
        input_path=str(corpus), output_path=str(out), checkpoint_dir=ckdir,
        chunk_bytes=16 * 1024, backend="cpu", num_shards=1, metrics=False,
        num_map_workers=1, max_retries=0, use_native=False, mapper="python",
    )
    base.update(kw)
    return JobConfig(**base)


def _map_step(workload):
    """The mapper method a python-route job maps each chunk with."""
    from map_oxidize_tpu.workloads.bigram import BigramMapper
    from map_oxidize_tpu.workloads.distinct import DistinctMapper
    from map_oxidize_tpu.workloads.inverted_index import InvertedIndexMapper

    return {"wordcount": (WordCountMapper, "map_chunk"),
            "bigram": (BigramMapper, "map_chunk"),
            "invertedindex": (InvertedIndexMapper, "map_docs"),
            "distinct": (DistinctMapper, "map_chunk")}[workload]


def _route_kw(route):
    return dict(mapper=route, use_native=route == "native")


# python-native: stopped on the python route, resumed on the native scan.
# Not for bigram: its native route maps hash-only, another checkpoint
# identity, so a python spill is discarded there rather than resumed.
_RESUME_CASES = [
    (w, r) for w in ("wordcount", "bigram", "invertedindex", "distinct")
    for r in ("python", "native")] + [
    (w, "python-native") for w in ("wordcount", "invertedindex", "distinct")]


@pytest.mark.parametrize("workload,route", _RESUME_CASES)
def test_resume_after_kill_byte_identical(tmp_path, monkeypatch, workload,
                                          route):
    """A job stopped after chunk k and run again writes the uninterrupted
    run's output byte for byte: the k saved chunks (a partial prefix of
    the spill) replay, only the rest maps again, and success removes the
    spill (reference cleanup semantics).  A checkpoint's identity leaves
    the route out, so the python splitter and the native scan must cut
    chunks alike for ``python-native`` to resume."""
    kill_route, _, resume_route = route.partition("-")
    resume_route = resume_route or kill_route
    if "native" in route:
        from map_oxidize_tpu.native.bindings import load_or_none

        if load_or_none() is None:
            pytest.skip("native build unavailable")
    corpus = tmp_path / "corpus.txt"
    _make_corpus(corpus)
    ckdir = str(tmp_path / "ck")
    kw = _route_kw(resume_route)

    # reference run: no checkpointing at all
    want_out = tmp_path / "want.txt"
    n_chunks = run_job(_cfg(corpus, want_out, None, **kw),
                       workload).metrics["chunks"]
    assert n_chunks >= 5, n_chunks

    # run 1: stopped after chunk k — the kill lands on chunk k's save
    k = 3
    real_save = CheckpointStore.save

    def dying_save(self, idx, out, next_offset):
        if idx == k:
            raise KeyboardInterrupt("simulated kill")
        real_save(self, idx, out, next_offset)

    got_out = tmp_path / "got.txt"
    with monkeypatch.context() as m:
        m.setattr(CheckpointStore, "save", dying_save)
        with pytest.raises(KeyboardInterrupt):
            run_job(_cfg(corpus, got_out, ckdir, **_route_kw(kill_route)),
                    workload)
    saved = sorted(n for n in os.listdir(ckdir) if n.endswith(".npz"))
    assert saved == [f"chunk_{i:06d}.npz" for i in range(k)], saved

    # run 2: resumes — must not re-map the spilled prefix.  Only run 2's
    # threads count: run 1's prefetch thread, abandoned at the kill, may
    # still be mapping a chunk it read ahead
    cls, step = _map_step(workload)
    real_step = getattr(cls, step)
    mapped = []
    run1_threads = set(threading.enumerate())

    def counting_step(self, *a):
        if threading.current_thread() not in run1_threads:
            mapped.append(1)
        return real_step(self, *a)

    monkeypatch.setattr(cls, step, counting_step)
    res = run_job(_cfg(corpus, got_out, ckdir, **kw), workload)
    assert got_out.read_bytes() == want_out.read_bytes()
    assert res.metrics["chunks"] == n_chunks
    assert res.metrics["checkpoint/chunks_replayed"] == k
    assert res.metrics["checkpoint/chunks_saved"] == n_chunks - k
    # the python route maps the tail through the mapper's own step; the
    # native scan maps in C++
    assert len(mapped) == (n_chunks - k if resume_route == "python" else 0)
    assert not os.path.isdir(ckdir)


def test_resume_preserves_prefix_only_words(tmp_path):
    """Regression: update() must not strip a chunk's pending dictionary
    delta before the checkpoint spill serializes it.  Words whose ONLY
    occurrences sit in the replayed prefix can never be re-drained on
    resume — if the spill lost them, finalize dies on a KeyError."""
    corpus = tmp_path / "c.txt"
    with open(corpus, "wb") as f:
        # unique early vocabulary (first ~3 chunks), disjoint tail vocab
        for i in range(600):
            f.write(b"early%04d " % i)
            if i % 8 == 7:
                f.write(b"\n")
        f.write(b"\n")
        for i in range(600):
            f.write(b"late%04d " % i)
            if i % 8 == 7:
                f.write(b"\n")
    ckdir = str(tmp_path / "ck")
    from map_oxidize_tpu.workloads.wordcount import WordCountMapper

    if WordCountMapper("ascii", use_native=True)._native is None:
        pytest.skip("native build unavailable; the pending-delta spill "
                    "path under test only exists on the native mapper")
    want = run_job(_cfg(corpus, tmp_path / "w.txt", None, use_native=True,
                        mapper="native", chunk_bytes=2048), "wordcount")

    # native-path run spills every chunk; keep the spill.  With the stolen-
    # delta bug, every spilled chunk carried an EMPTY dictionary here.
    run_job(_cfg(corpus, tmp_path / "g.txt", ckdir, use_native=True,
                 mapper="native", chunk_bytes=2048, keep_intermediates=True),
            "wordcount")
    # pure-replay run: every chunk comes from the spill, nothing is
    # re-mapped, so lost dictionary deltas cannot be re-drained -> KeyError
    res = run_job(_cfg(corpus, tmp_path / "g2.txt", ckdir, use_native=True,
                       mapper="native", chunk_bytes=2048,
                       keep_intermediates=True), "wordcount")
    assert res.counts == want.counts
    assert (tmp_path / "g2.txt").read_bytes() == (tmp_path / "w.txt").read_bytes()


def test_keep_intermediates_preserves_spill(tmp_path):
    corpus = tmp_path / "corpus.txt"
    _make_corpus(corpus, n_lines=500)
    ckdir = str(tmp_path / "ck")
    run_job(_cfg(corpus, tmp_path / "o.txt", ckdir, keep_intermediates=True),
            "wordcount")
    names = os.listdir(ckdir)
    assert "meta.json" in names
    assert any(n.endswith(".npz") for n in names)

    # a second identical run replays everything and still matches
    res = run_job(_cfg(corpus, tmp_path / "o2.txt", ckdir,
                       keep_intermediates=True), "wordcount")
    assert (tmp_path / "o.txt").read_bytes() == (tmp_path / "o2.txt").read_bytes()
    assert res.metrics["chunks"] > 0


def test_checkpoint_invalidated_on_different_job(tmp_path):
    corpus = tmp_path / "corpus.txt"
    _make_corpus(corpus, n_lines=500)
    other = tmp_path / "other.txt"
    _make_corpus(other, n_lines=700, seed=1)
    ckdir = str(tmp_path / "ck")

    run_job(_cfg(corpus, tmp_path / "o.txt", ckdir, keep_intermediates=True),
            "wordcount")
    # same dir, different input: stale spill must be discarded, not replayed
    res = run_job(_cfg(other, tmp_path / "o2.txt", ckdir), "wordcount")
    want = run_job(_cfg(other, tmp_path / "o3.txt", None), "wordcount")
    assert res.counts == want.counts


def test_round_robin_mode_resumes_by_index(tmp_path):
    corpus = tmp_path / "corpus.txt"
    _make_corpus(corpus, n_lines=800)
    ckdir = str(tmp_path / "ck")
    from map_oxidize_tpu.runtime.driver import run_wordcount_job
    from map_oxidize_tpu.api import SumReducer

    want = run_job(_cfg(corpus, tmp_path / "w.txt", None, num_chunks=6),
                   "wordcount")
    dying = _DyingMapper(die_after=2, use_native=False)
    with pytest.raises(KeyboardInterrupt):
        run_wordcount_job(_cfg(corpus, tmp_path / "g.txt", ckdir,
                               num_chunks=6), dying, SumReducer())
    counting = _DyingMapper(die_after=10**9, use_native=False)
    res = run_wordcount_job(_cfg(corpus, tmp_path / "g.txt", ckdir,
                                 num_chunks=6), counting, SumReducer())
    assert counting.mapped == 4  # 6 chunks, 2 replayed
    assert res.counts == want.counts


def test_meta_mismatch_detection(tmp_path):
    corpus = tmp_path / "c.txt"
    _make_corpus(corpus, n_lines=100)
    cfg = _cfg(corpus, "", str(tmp_path / "ck"))
    m1 = CheckpointStore.job_meta(cfg, "wordcount")
    m2 = CheckpointStore.job_meta(cfg, "bigram")
    assert m1 != m2
    m3 = CheckpointStore.job_meta(
        _cfg(corpus, "", None, chunk_bytes=8 * 1024), "wordcount")
    assert m1 != m3

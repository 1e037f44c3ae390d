"""The native mmap scan fanned out over several streams
(``native/build.py`` ``OrderedScan``) against the one-stream scan.

The contract: whatever the number of workers, the consumer sees the
one-stream scan's items in file order — every chunk's rows, records and
cut offset identical, and each dictionary key handed on once, by the
chunk that first holds it.  Errors surface in file order, and a scan
that is closed or dropped leaves no thread and no mapping behind."""

import gc
import threading
import time

import numpy as np
import pytest

from map_oxidize_tpu.native.bindings import load_or_none

native = load_or_none()
pytestmark = pytest.mark.skipif(native is None,
                                reason="native build unavailable")

if native is not None:
    from map_oxidize_tpu.native.build import (
        DictUnion,
        NativeStream,
        StreamPool,
    )
    from map_oxidize_tpu.ops.hashing import HashDictionary

CHUNK = 96  # bytes: a few hundred ranges over the corpora below


def _words(rng, unicode: bool, n: int) -> list[bytes]:
    alpha = (["a", "B", "c", "é", "Σ", "中", "x,", "Y."] if unicode
             else ["a", "B", "c", "d", "E", "x,", "Y."])
    return ["".join(rng.choice(alpha, size=int(rng.integers(1, 7))))
            .encode("utf-8") for _ in range(n)]


def _corpus(path, shape: str, unicode: bool, seed: int = 0):
    """``lines``: newline cuts; ``spaces``: one long line, whitespace
    cuts; ``solid``: whitespace-free runs longer than a chunk, hard cuts
    (in unicode mode across multi-byte characters)."""
    rng = np.random.default_rng(seed)
    vocab = _words(rng, unicode, 300)
    pick = lambda k: [vocab[int(i)] for i in rng.integers(0, len(vocab), k)]
    if shape == "lines":
        body = b"".join(b" ".join(pick(int(rng.integers(1, 9)))) + b"\n"
                        for _ in range(1500))
    elif shape == "spaces":
        body = b" \t".join(pick(4000))
    else:
        unit = "é中Σ".encode("utf-8") if unicode else b"solid"
        body = b"".join(unit * int(rng.integers(30, 90)) + b" "
                        + b" ".join(pick(3)) + b" " for _ in range(200))
    path.write_bytes(body)
    return path


def _one_stream(kind, path, tokenizer, start):
    s = NativeStream(2 if kind == "hashes" else 1, tokenizer)
    try:
        if kind == "words":
            return list(s.iter_file(str(path), CHUNK, start))
        if kind == "hashes":
            return list(s.iter_file_hashes(str(path), CHUNK, start))
        return list(s.iter_file_hll(str(path), CHUNK, 8, start))
    finally:
        s.close()


def _fanned(kind, path, tokenizer, start, workers):
    pool = StreamPool(2 if kind == "hashes" else 1, tokenizer)
    if kind == "words":
        return list(pool.iter_file(str(path), CHUNK, start, workers))
    if kind == "hashes":
        return list(pool.iter_file_hashes(str(path), CHUNK, start, workers))
    return list(pool.iter_file_hll(str(path), CHUNK, 8, start, workers))


def _scan_threads():
    return [t for t in threading.enumerate()
            if t.name.startswith("scan-") and t.is_alive()]


@pytest.mark.parametrize("start", ["head", "resume"])
@pytest.mark.parametrize("shape", ["lines", "spaces", "solid"])
@pytest.mark.parametrize("tokenizer", ["ascii", "unicode"])
@pytest.mark.parametrize("kind", ["words", "hashes", "hll"])
@pytest.mark.parametrize("workers", [1, 2, 4])
def test_fanout_matches_one_stream(tmp_path, workers, kind, tokenizer,
                                   shape, start):
    path = _corpus(tmp_path / "c.txt", shape, tokenizer == "unicode")
    off = 0
    if start == "resume":
        # a previous run's cut boundary, a third of the way in
        cuts = [item[-1] for item in _one_stream(kind, path, tokenizer, 0)]
        off = cuts[len(cuts) // 3]
    want = _one_stream(kind, path, tokenizer, off)
    got = _fanned(kind, path, tokenizer, off, workers)
    assert len(want) > 20
    assert [g[-1] for g in got] == [w[-1] for w in want]  # cut offsets
    if kind == "hll":
        for (gr, gn, _), (wr, wn, _) in zip(got, want):
            assert gn == wn
            assert gr.tobytes() == wr.tobytes()
        return
    union_got, union_want = {}, {}
    for (g, _), (w, _) in zip(got, want):
        assert g.records_in == w.records_in
        if kind == "hashes":
            assert g.keys64.tobytes() == w.keys64.tobytes()
            continue
        assert g.hi.tobytes() == w.hi.tobytes()
        assert g.lo.tobytes() == w.lo.tobytes()
        assert g.values.tobytes() == w.values.tobytes()
        # each key handed on by the chunk that first holds it
        assert dict(g.dictionary.items()) == dict(w.dictionary.items())
        union_got.update(g.dictionary.items())
        union_want.update(w.dictionary.items())
    assert union_got == union_want


def test_fanout_stress_more_workers_than_cores(tmp_path):
    """Sixteen workers, tiny ranges and a short switch interval: a lost
    update in the claim, commit or take would drop, repeat or reorder a
    range, or hand a key on twice."""
    import os
    import sys

    path = _corpus(tmp_path / "c.txt", "lines", False, seed=3)
    want = _one_stream("words", path, "ascii", 0)
    workers = max(16, 2 * (os.cpu_count() or 1))
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        t = time.time()
        for _ in range(3):
            got = list(StreamPool().iter_file(str(path), CHUNK, 0, workers))
            assert [g[1] for g in got] == [w[1] for w in want]
            assert [g[0].records_in for g in got] == \
                [w[0].records_in for w in want]
            rows = sum(g[0].dictionary._pending_rows for g in got)
            assert rows == len({h for w in want
                                for h in w[0].dictionary.to_arrays()[0]
                                .tolist()})
        assert time.time() - t < 60
    finally:
        sys.setswitchinterval(old)
    assert not _wait_no_scan_threads()


def test_rows_do_not_depend_on_stream_history(tmp_path):
    """A stream whose chunk table grew on a wide first chunk emits a later
    chunk's rows in the same order as a fresh stream: first occurrence in
    the chunk, whatever table capacity the stream reached."""
    wide = b" ".join(b"w%d" % i for i in range(40_000)) + b"\n"
    narrow = b"".join(b"b a c a b d e f g\n" for _ in range(4000))
    path = tmp_path / "c.txt"
    path.write_bytes(wide + narrow)
    chunk = len(wide)

    def scan(start):
        s = NativeStream()
        try:
            return list(s.iter_file(str(path), chunk, start))
        finally:
            s.close()

    whole, fresh = scan(0), scan(chunk)
    assert len(whole) == 2 and whole[0][1] == chunk
    (w, _), (f, _) = whole[1], fresh[0]
    assert w.hi.tobytes() == f.hi.tobytes()
    assert w.lo.tobytes() == f.lo.tobytes()
    assert w.values.tobytes() == f.values.tobytes()
    got = list(StreamPool().iter_file(str(path), chunk, 0, 2))
    for (g, _), (w, _) in zip(got, whole):
        assert g.hi.tobytes() == w.hi.tobytes()
        assert g.values.tobytes() == w.values.tobytes()


def test_dictionary_rows_are_the_distinct_keys(tmp_path):
    """Four streams each drain most of the vocabulary; the consumer gets
    each key once."""
    path = _corpus(tmp_path / "c.txt", "lines", False)
    items = list(StreamPool().iter_file(str(path), CHUNK, 0, 4))
    rows = sum(out.dictionary._pending_rows for out, _ in items)
    union = HashDictionary()
    for out, _ in items:
        union.update(out.dictionary)
    assert rows == len(union)


def test_invalid_utf8_raises_after_the_ranges_before_it(tmp_path):
    path = _corpus(tmp_path / "c.txt", "lines", True)
    body = path.read_bytes()
    at = body.index(b"\n", len(body) // 2) + 1
    path.write_bytes(body[:at] + b"bad \xff\xfe bytes\n" + body[at:])
    serial = []
    with pytest.raises(UnicodeDecodeError):
        for item in NativeStream(1, "unicode").iter_file(str(path), CHUNK):
            serial.append(item[1])
    got = []
    scan = StreamPool(1, "unicode").iter_file(str(path), CHUNK, 0, 4)
    with pytest.raises(UnicodeDecodeError):
        for item in scan:
            got.append(item[1])
    assert got == serial and len(got) > 10
    assert not _scan_threads()


def _wait_no_scan_threads():
    deadline = time.time() + 10
    while _scan_threads() and time.time() < deadline:
        time.sleep(0.01)
    return _scan_threads()


@pytest.mark.parametrize("how", ["close", "drop", "prefetcher"])
def test_abandoned_scan_leaves_no_threads(tmp_path, how):
    from map_oxidize_tpu.runtime.pipeline import pipelined

    path = _corpus(tmp_path / "c.txt", "lines", False)
    scan = StreamPool().iter_file(str(path), CHUNK, 0, 4)
    if how == "prefetcher":
        it = pipelined(scan, 2)
        next(it)
        next(it)
        it.close()
    else:
        next(scan)
        next(scan)
        assert _scan_threads()
        if how == "close":
            scan.close()
        else:
            del scan
            gc.collect()
    assert not _wait_no_scan_threads()


def _delta(pairs):
    d = HashDictionary()
    d.add_arrays(np.array([h for h, _ in pairs], np.uint64),
                 np.array([len(t) for _, t in pairs], np.int64),
                 b"".join(t for _, t in pairs))
    return d


def test_dict_union_drops_true_repeats():
    u = DictUnion()
    first = u.novel(_delta([(5, b"five"), (1, b"one"), (9, b"nine")]))
    assert dict(first.items()) == {5: b"five", 1: b"one", 9: b"nine"}
    second = u.novel(_delta([(9, b"nine"), (3, b"three"), (1, b"one")]))
    assert dict(second.items()) == {3: b"three"}
    assert u.dropped == 2
    assert len(u.novel(_delta([(3, b"three"), (5, b"five")]))) == 0
    assert u.dropped == 4


@pytest.mark.parametrize("other", [b"nina", b"nine!", b"nin"])
def test_dict_union_raises_on_same_hash_other_bytes(other):
    u = DictUnion()
    u.novel(_delta([(1, b"one"), (9, b"nine")]))
    with pytest.raises(ValueError, match="64-bit hash collision"):
        u.novel(_delta([(1, b"one"), (9, other)]))


def test_native_kill_resume_byte_identical(tmp_path):
    """A job killed mid-map on the fanned-out scan spills exactly the
    chunks handed on before the kill; the resume's output is the
    uninterrupted run's, byte for byte."""
    import os

    from map_oxidize_tpu.api import SumReducer
    from map_oxidize_tpu.config import JobConfig
    from map_oxidize_tpu.runtime.driver import run_wordcount_job
    from map_oxidize_tpu.workloads.wordcount import WordCountMapper

    class _DyingMapper(WordCountMapper):
        def map_file(self, *a, **kw):
            scan = super().map_file(*a, **kw)

            def _die():
                try:
                    for i, item in enumerate(scan):
                        if i == 3:
                            raise KeyboardInterrupt("simulated kill")
                        yield item
                finally:
                    scan.close()
            return _die()

    corpus = _corpus(tmp_path / "c.txt", "lines", False)
    ckdir = str(tmp_path / "ck")

    def cfg(out, **kw):
        return JobConfig(input_path=str(corpus), output_path=str(out),
                         backend="cpu", num_shards=1, metrics=True,
                         chunk_bytes=4096, num_map_workers=4, **kw)

    want = tmp_path / "want.txt"
    run_wordcount_job(cfg(want), WordCountMapper(), SumReducer())
    got = tmp_path / "got.txt"
    with pytest.raises(KeyboardInterrupt):
        run_wordcount_job(cfg(got, checkpoint_dir=ckdir), _DyingMapper(),
                          SumReducer())
    saved = [n for n in os.listdir(ckdir) if n.endswith(".npz")]
    assert len(saved) == 3, saved
    assert not _wait_no_scan_threads()
    run_wordcount_job(cfg(got, checkpoint_dir=ckdir), WordCountMapper(),
                      SumReducer())
    assert got.read_bytes() == want.read_bytes()


def test_job_reports_scan_workers_and_dedup(tmp_path):
    from map_oxidize_tpu.runtime import run_job
    from map_oxidize_tpu.runtime.stream import scan_workers
    from map_oxidize_tpu.config import JobConfig

    corpus = _corpus(tmp_path / "c.txt", "lines", False)
    cfg = JobConfig(input_path=str(corpus), output_path="", backend="cpu",
                    num_shards=1, metrics=True, chunk_bytes=4096,
                    num_map_workers=4, mapper="native")
    m = run_job(cfg, "wordcount").metrics
    k = min(scan_workers(cfg), -(-corpus.stat().st_size // 4096))
    assert m["map/scan_workers"] == k
    assert m["map/dict_rows_deduped"] >= 0
    if k > 1:
        assert m["map/dict_rows_deduped"] > 0
    # one produce span per range, timed on the workers
    assert m["span/map/produce_n"] == m["chunks"]

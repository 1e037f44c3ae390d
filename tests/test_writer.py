"""The columnar ``final_result.txt`` writer against the row formula it
replaces: byte order, decimal rendering, the bounded sort matrix, the
tie path, and the readback's columns."""

import random
import tracemalloc

import numpy as np
import pytest

from map_oxidize_tpu.config import JobConfig
from map_oxidize_tpu.io.writer import CountColumns, write_final_result
from map_oxidize_tpu.ops.hashing import HashDictionary, moxt64
from map_oxidize_tpu.runtime import run_job
from map_oxidize_tpu.runtime.driver import LazyCounts
from map_oxidize_tpu.workloads.reference_model import wordcount_model


def _reference(pairs) -> bytes:
    return b"".join(w + b" " + str(c).encode() + b"\n"
                    for w, c in sorted(pairs))


def _random_words(n: int, seed: int) -> list[tuple[bytes, int]]:
    rng = random.Random(seed)
    alphabet = b"abcdefghijklmnopqrstuvwxyz.,;'\x00\x7f\x80\xff"
    words = {bytes(rng.choices(alphabet, k=rng.randint(1, 40)))
             for _ in range(n)}
    return [(w, rng.randint(1, 10 ** 7)) for w in words]


CASES = {
    "empty": [],
    "one_row": [(b"solo", 7)],
    "prefix_chains": [(b"a", 1), (b"a\x00", 2), (b"a\x00\x00", 3),
                      (b"ab", 4), (b"", 5), (b"\x00", 6), (b"a" * 8, 7),
                      (b"a" * 8 + b"\x00", 8), (b"a" * 9, 9)],
    "high_bytes": [(bytes([x, y]), x + y) for x in range(256)
                   for y in (0x00, 0x7f, 0x80, 0xff)],
    "count_edges": [(b"w%d" % i, c) for i, c in enumerate(
        [0, -1, 1, 9, 10, -10, 2 ** 31, -2 ** 31, 10 ** 18, 10 ** 18 - 1,
         2 ** 63 - 1, -2 ** 63])],
    "random_250k": _random_words(250_000, 28),
}


@pytest.mark.parametrize("case", list(CASES))
def test_columnar_writer_byte_identical(case, tmp_path):
    """Rows, and columns in any order, are written as the row formula
    writes them; the columns iterate as the sorted pairs."""
    pairs = CASES[case]
    want = _reference(pairs)
    out = tmp_path / "final_result.txt"
    assert write_final_result(str(out), iter(pairs)) == len(pairs)
    assert out.read_bytes() == want
    shuffled = list(pairs)
    random.Random(1).shuffle(shuffled)
    lens = np.array([len(w) for w, _ in shuffled], np.int64)
    blob = np.frombuffer(b"".join(w for w, _ in shuffled), np.uint8)
    rows = CountColumns(blob, lens, np.array([c for _, c in shuffled]))
    assert write_final_result(str(out), rows) == len(pairs)
    assert out.read_bytes() == want
    assert rows.tie_rows == 0
    assert list(rows) == sorted(pairs)


def test_huge_token_bounded_memory_and_tie_rows(tmp_path):
    """A 4 MiB token among 10^5 short words: the sort matrix is bounded by
    the prefix width, not the longest word, and only the rows that tie on
    that prefix while running past it take the per-row path."""
    big = b"x" * (4 << 20)
    tied = [(big, 1), (big[:-1] + b"y", 2), (b"x" * 40, 3), (b"x" * 32, 4)]
    untied = [(b"x" * 31, 5), (b"y" + big, 6)]
    short = [(b"%07d" % i, i) for i in range(100_000)]
    pairs = short + tied + untied
    n = len(pairs)
    lens = np.array([len(w) for w, _ in pairs], np.int64)
    blob = np.frombuffer(b"".join(w for w, _ in pairs), np.uint8)
    counts = np.array([c for _, c in pairs], np.int64)
    out = tmp_path / "final_result.txt"
    tracemalloc.start()
    try:
        rows = CountColumns(blob, lens, counts)
        assert write_final_result(str(out), rows) == n
        peak = tracemalloc.get_traced_memory()[1]
    finally:
        tracemalloc.stop()
    assert rows.tie_rows == len(tied)
    assert out.read_bytes() == _reference(pairs)
    # linear in the bytes and rows written; rows x longest word would be
    # ~400 GiB
    assert peak < 16 * blob.size + 512 * n


def _lazy(words: list[bytes], hashes: list[int], vals: list[int]):
    d = HashDictionary()
    for h, w in zip(hashes, words):
        d.add(h, w)
    return LazyCounts(np.array(hashes, np.uint64), np.array(vals, np.int32),
                      d)


def test_lazycounts_columns_match_items(tmp_path):
    words = [w for w, _ in _random_words(5000, 3)]
    hashes = [moxt64(w) for w in words]
    lc = _lazy(words, hashes, list(range(len(words))))
    # a dictionary entry no live key holds is left out
    lc._dict.add(moxt64(b"not-live"), b"not-live")
    out = tmp_path / "final_result.txt"
    rows = CountColumns(*lc.columns())
    assert write_final_result(str(out), rows) == len(words)
    assert out.read_bytes() == _reference(lc.items())


@pytest.mark.parametrize("word", [b"same", b"long" * 20])
def test_two_live_keys_one_word_raise(word, tmp_path):
    lc = _lazy([word, b"other", word], [11, 22, 33], [1, 2, 3])
    msg = "readback found 2 distinct words for 3 live keys"
    with pytest.raises(RuntimeError, match=msg):
        write_final_result(str(tmp_path / "o.txt"),
                           CountColumns(*lc.columns()))


def test_live_key_missing_from_dictionary_raises():
    lc = _lazy([b"a"], [11], [1])
    lc._k64 = np.array([11, 12], np.uint64)
    lc._vals = np.array([1, 2], np.int32)
    with pytest.raises(KeyError):
        lc.columns()


@pytest.mark.parametrize("mapper", ["native", "python"])
def test_wordcount_job_writes_reference_bytes(mapper, tmp_path):
    corpus = tmp_path / "corpus.txt"
    rng = random.Random(7)
    vocab = [bytes(rng.choices(b"abcdefgh", k=rng.randint(1, 9)))
             + rng.choice([b"", b",", b"."]) for _ in range(3000)]
    text = b"\n".join(b" ".join(rng.choices(vocab, k=12)) for _ in range(2000))
    corpus.write_bytes(text)
    out = tmp_path / "final_result.txt"
    cfg = JobConfig(input_path=str(corpus), output_path=str(out),
                    chunk_bytes=16 << 10, backend="cpu", mapper=mapper)
    res = run_job(cfg, "wordcount")
    model = wordcount_model([text])
    assert res.metrics["map/mapper"] == mapper
    assert out.read_bytes() == _reference(model.items())
    assert res.metrics["write/rows"] == len(model) == len(res.counts)
    assert res.metrics["write/tie_rows"] == 0

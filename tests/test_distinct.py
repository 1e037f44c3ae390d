"""Distinct-count workload (HyperLogLog): estimator accuracy vs the exact
oracle, register math, python/native mapper parity, 1-vs-8-shard register
identity, and checkpoint/resume."""

import numpy as np
import pytest

from map_oxidize_tpu.config import JobConfig
from map_oxidize_tpu.runtime import run_job
from map_oxidize_tpu.workloads.distinct import (
    DistinctMapper,
    distinct_model,
    hll_estimate,
    hll_registers,
)


def _corpus(tmp_path, n_lines=3000, vocab=5000, seed=0, name="c.txt"):
    rng = np.random.default_rng(seed)
    words = [b"w%05d" % i for i in range(vocab)]
    p = tmp_path / name
    with open(p, "wb") as f:
        for _ in range(n_lines):
            f.write(b" ".join(words[int(i)]
                              for i in rng.integers(0, vocab, 8)) + b"\n")
    return p


def _cfg(corpus, **kw):
    base = dict(input_path=str(corpus), output_path="", backend="cpu",
                num_shards=1, metrics=False, chunk_bytes=16 * 1024)
    base.update(kw)
    return JobConfig(**base)


def test_registers_match_reference_definition(rng):
    """hll_registers == a per-hash Python model of bucket/rank."""
    hashes = rng.integers(0, 2**64, size=20_000, dtype=np.uint64)
    p = 11
    regs = hll_registers(hashes, p)
    want = np.zeros(1 << p, np.int32)
    for h in hashes.tolist():
        b = h >> (64 - p)
        w = h & ((1 << (64 - p)) - 1)
        rank = (64 - p) + 1 if w == 0 else (64 - p) - w.bit_length() + 1
        want[b] = max(want[b], rank)
    np.testing.assert_array_equal(regs, want)


def test_estimate_accuracy_synthetic(rng):
    """~100k uniform hashes: estimate within 4 sigma of exact (rse
    1.04/sqrt(2^14) ~ 0.81%)."""
    n = 100_000
    hashes = rng.integers(0, 2**64, size=n, dtype=np.uint64)
    est = hll_estimate(hll_registers(hashes, 14))
    assert abs(est - n) / n < 0.033


def test_small_range_linear_counting(rng):
    """Cardinalities far below m use the zero-register correction and are
    near-exact."""
    hashes = rng.integers(0, 2**64, size=200, dtype=np.uint64)
    est = hll_estimate(hll_registers(hashes, 14))
    assert abs(est - 200) < 6


def test_job_estimate_matches_oracle(tmp_path):
    corpus = _corpus(tmp_path)
    res = run_job(_cfg(corpus), "distinct")
    with open(corpus, "rb") as f:
        exact = distinct_model([f.read()])
    assert 4000 < exact <= 5000  # most of the vocabulary gets drawn
    assert abs(res.estimate - exact) / exact < 0.033


def test_python_native_registers_identical(tmp_path):
    corpus = _corpus(tmp_path, n_lines=500)
    nat = DistinctMapper("ascii", use_native=True, p=12)
    if nat._native is None:
        pytest.skip("native build unavailable")
    py = DistinctMapper("ascii", use_native=False, p=12)
    chunk = open(corpus, "rb").read()
    a, b = nat.map_chunk(chunk), py.map_chunk(chunk)
    np.testing.assert_array_equal(a.lo, b.lo)
    np.testing.assert_array_equal(a.values, b.values)
    assert a.records_in == b.records_in


def test_sharded_registers_equal_single(tmp_path):
    """Max is associative/commutative: the 8-shard mesh run must produce
    bit-identical registers (and therefore the identical estimate)."""
    corpus = _corpus(tmp_path, n_lines=1500)
    r1 = run_job(_cfg(corpus), "distinct")
    r8 = run_job(_cfg(corpus, num_shards=8), "distinct")
    np.testing.assert_array_equal(r1.registers, r8.registers)
    assert r1.estimate == r8.estimate


def test_distinct_checkpoint_resume(tmp_path):
    """Standard per-chunk spill/replay: a full spilled run replayed into a
    fresh engine reproduces the identical registers."""
    import os

    corpus = _corpus(tmp_path, n_lines=1200)
    ck = str(tmp_path / "ck")
    want = run_job(_cfg(corpus), "distinct")
    got1 = run_job(_cfg(corpus, checkpoint_dir=ck, keep_intermediates=True),
                   "distinct")
    assert os.path.isdir(ck)
    got2 = run_job(_cfg(corpus, checkpoint_dir=ck), "distinct")  # pure replay
    np.testing.assert_array_equal(got1.registers, want.registers)
    np.testing.assert_array_equal(got2.registers, want.registers)
    assert not os.path.isdir(ck)  # success removes the spill by default


def test_distinct_streams_under_num_chunks(tmp_path):
    """``num_chunks`` (the word count's round-robin split) leaves distinct
    on its streamed byte-range split: same chunks, same registers."""
    corpus = _corpus(tmp_path)
    want = run_job(_cfg(corpus), "distinct")
    got = run_job(_cfg(corpus, num_chunks=4), "distinct")
    assert got.metrics["chunks"] == want.metrics["chunks"] > 4
    np.testing.assert_array_equal(got.registers, want.registers)


def test_unions_merge_by_max(tmp_path):
    """Registers from two disjoint corpora merged with np.maximum estimate
    the union — the HLL mergeability property the sharded path relies on."""
    c1 = _corpus(tmp_path, vocab=3000, seed=1, name="a.txt")
    rng = np.random.default_rng(2)
    words = [b"x%05d" % i for i in range(3000)]  # disjoint vocabulary
    c2 = tmp_path / "b.txt"
    with open(c2, "wb") as f:
        for _ in range(3000):
            f.write(b" ".join(words[int(i)]
                              for i in rng.integers(0, 3000, 8)) + b"\n")
    r1 = run_job(_cfg(c1), "distinct")
    r2 = run_job(_cfg(c2), "distinct")
    est = hll_estimate(np.maximum(r1.registers, r2.registers))
    assert abs(est - 6000) / 6000 < 0.04


def test_output_files(tmp_path):
    """distinct writes its result: a text summary by default, the raw
    (mergeable) registers for a .npy output path."""
    corpus = _corpus(tmp_path, n_lines=300)
    res = run_job(_cfg(corpus, output_path=str(tmp_path / "est.txt")),
                  "distinct")
    lines = dict(ln.split("\t") for ln in
                 (tmp_path / "est.txt").read_text().splitlines())
    assert float(lines["estimate"]) == pytest.approx(res.estimate, abs=0.1)
    assert int(lines["precision"]) == 14
    res2 = run_job(_cfg(corpus, output_path=str(tmp_path / "regs.npy")),
                   "distinct")
    np.testing.assert_array_equal(np.load(tmp_path / "regs.npy"),
                                  res2.registers)


def test_registers_high_precision_branch(rng):
    """p > 16 uses the bounded-scratch maximum.at fold: same registers as
    the bincount formulation computed at the same p via the model."""
    hashes = rng.integers(0, 2**64, size=30_000, dtype=np.uint64)
    p = 17
    regs = hll_registers(hashes, p)
    want = np.zeros(1 << p, np.int32)
    for h in hashes.tolist():
        b = h >> (64 - p)
        w = h & ((1 << (64 - p)) - 1)
        rank = (64 - p) + 1 if w == 0 else (64 - p) - w.bit_length() + 1
        want[b] = max(want[b], rank)
    np.testing.assert_array_equal(regs, want)


def test_native_hll_fold_matches_hash_extraction(tmp_path, rng):
    """The in-scan C++ register fold must equal hll_registers() applied to
    the raw hash stream of the same chunks — for several p values, both
    tokenizers, and the mmap file iterator (same cut offsets as the
    hash-only scan)."""
    from map_oxidize_tpu.native import bindings

    if bindings.load_or_none() is None:
        pytest.skip("native build unavailable")
    from map_oxidize_tpu.native.build import NativeStream

    blob = b"\n".join(
        b" ".join(b"t%04x" % int(v) for v in rng.integers(0, 1 << 16, 12))
        for _ in range(400)) + b"\n \n\tmixed  WS\r\n"
    for tokenizer in ("ascii", "unicode"):
        s = NativeStream(1, tokenizer)
        try:
            for p in (11, 14, 18):
                regs, nt = s.map_chunk_hll(blob, p)
                out = s.map_chunk_hashes(blob)
                assert nt == out.records_in
                np.testing.assert_array_equal(
                    regs.astype(np.int32), hll_registers(out.keys64, p))
        finally:
            s.close()
    path = tmp_path / "hll.txt"
    path.write_bytes(blob * 8)
    s = NativeStream(1, "ascii")
    try:
        folded = list(s.iter_file_hll(str(path), 4096, 12))
        raw = list(s.iter_file_hashes(str(path), 4096))
        assert [off for _, _, off in folded] == [off for _, off in raw]
        acc = np.zeros(1 << 12, np.int32)
        for regs, _, _ in folded:
            acc = np.maximum(acc, regs.astype(np.int32))
        want = hll_registers(
            np.concatenate([o.keys64 for o, _ in raw]), 12)
        np.testing.assert_array_equal(acc, want)
    finally:
        s.close()

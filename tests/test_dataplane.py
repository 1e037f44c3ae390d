"""Data-plane observatory (ISSUE-16 tentpole): row-conservation audits,
key-skew telemetry, and reduction-ratio gauges across the shuffle.

Layers covered:

* checksum algebra — order independence, sum-combine invariance, and
  single-row sensitivity of both digest families;
* partition parity — the audit's numpy partitioner vs the device
  shuffle's ``bucket_of`` routing;
* the audit object — skew figures against numpy oracles on an
  adversarial Zipf corpus, HLL tolerance, violation raising, and the
  simulated cross-process reduction;
* end-to-end — single-chip wordcount (gauges + metrics doc + ledger
  gauge), the spilled inverted index, and an injected single-row drop
  that must fail the run with the NAMED error;
* the ledger diff gates and the skew SLO rule's evidence field;
* 2-process Gloo — wordcount + forced-spill inverted index in ONE child
  pair: per-partition rows, checksums matching across the exchange,
  the imbalance factor, and process-identical audit documents.
"""

import json
import os
import socket
import subprocess
import sys

import numpy as np
import pytest

from map_oxidize_tpu.obs import dataplane as dpm
from map_oxidize_tpu.obs.dataplane import (
    ConservationError,
    DataPlaneAudit,
    pair_digest,
    partition_of,
    weighted_checksum,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# --- checksum algebra -----------------------------------------------------


def test_weighted_checksum_order_independent():
    rng = np.random.default_rng(7)
    keys = rng.integers(0, 1 << 63, 500, dtype=np.uint64)
    vals = rng.integers(1, 100, 500, dtype=np.int64)
    perm = rng.permutation(500)
    assert (weighted_checksum(keys, vals)
            == weighted_checksum(keys[perm], vals[perm]))


def test_weighted_checksum_combine_invariant():
    # pre-combining rows of one key (summing values) must not change the
    # digest — the property that lets map-side pre-combined chunks match
    # the fully reduced readback
    keys = np.array([11, 11, 11, 42, 42], np.uint64)
    vals = np.array([1, 2, 3, 10, 20], np.int64)
    combined_k = np.array([11, 42], np.uint64)
    combined_v = np.array([6, 30], np.int64)
    assert (weighted_checksum(keys, vals)
            == weighted_checksum(combined_k, combined_v))


def test_weighted_checksum_single_drop_flips():
    keys = np.arange(1, 100, dtype=np.uint64)
    vals = np.ones(99, np.int64)
    assert (weighted_checksum(keys, vals)
            != weighted_checksum(keys[:-1], vals[:-1]))


def test_pair_digest_multiset_identity_and_sensitivity():
    rng = np.random.default_rng(3)
    keys = rng.integers(0, 1 << 62, 300, dtype=np.uint64)
    docs = rng.integers(0, 1 << 40, 300).astype(np.int64)
    perm = rng.permutation(300)
    assert pair_digest(keys, docs) == pair_digest(keys[perm], docs[perm])
    assert pair_digest(keys, docs) != pair_digest(keys[:-1], docs[:-1])
    # corrupting ONE doc id flips it too
    docs2 = docs.copy()
    docs2[17] += 1
    assert pair_digest(keys, docs) != pair_digest(keys, docs2)


def test_partition_of_matches_device_bucket_of():
    from map_oxidize_tpu.parallel.shuffle import bucket_of

    rng = np.random.default_rng(5)
    keys = rng.integers(0, np.iinfo(np.uint64).max, 1000,
                        dtype=np.uint64)
    hi = (keys >> np.uint64(32)).astype(np.uint32)
    lo = keys.astype(np.uint32)
    for s in (2, 4, 8):
        dev = np.asarray(bucket_of(hi, lo, s))
        assert np.array_equal(partition_of(keys, s), dev.astype(np.int64))


# --- the audit object -----------------------------------------------------


def _zipf_corpus(n=20000, vocab=200, seed=2):
    """Adversarial skew: one key owns ~half the rows."""
    rng = np.random.default_rng(seed)
    body = rng.integers(1, vocab, n, dtype=np.uint64) * np.uint64(2654435761)
    hot = np.full(n, body[0], np.uint64)
    take_hot = rng.random(n) < 0.5
    return np.where(take_hot, hot, body)


def test_audit_skew_matches_numpy_oracle():
    keys = _zipf_corpus()
    vals = np.ones(keys.shape[0], np.int64)
    a = DataPlaneAudit(8)
    # feed in 3 chunks to exercise accumulation
    for blk in np.array_split(np.arange(keys.shape[0]), 3):
        a.record_fold_in(keys[blk], vals[blk])
    rows = np.bincount(partition_of(keys, 8), minlength=8)
    d = a.doc()
    assert d["skew"]["rows_per_partition"] == rows.tolist()
    oracle_imb = rows.max() / rows.mean()
    assert d["skew"]["imbalance_factor"] == pytest.approx(oracle_imb,
                                                          rel=1e-3)
    n_distinct = np.unique(keys).shape[0]
    assert d["skew"]["distinct_est"] == pytest.approx(n_distinct, rel=0.05)
    # the hot key (~half the rows) must top the hot-key table exactly
    uk, cnt = np.unique(keys, return_counts=True)
    top_hash, top_rows = int(uk[cnt.argmax()]), int(cnt.max())
    hot = d["skew"]["hot_keys"][0]
    assert hot["hash"] == f"{top_hash:#018x}"
    assert hot["rows"] == top_rows
    assert d["skew"]["top_share"] == pytest.approx(
        top_rows / keys.shape[0], abs=1e-3)


def test_audit_fold_conservation_and_violation():
    keys = _zipf_corpus(4000, 50, seed=9)
    vals = np.ones(keys.shape[0], np.int64)
    a = DataPlaneAudit(4)
    a.record_fold_in(keys, vals)
    uk, inv = np.unique(keys, return_inverse=True)
    reduced = np.bincount(inv).astype(np.int64)
    a.record_fold_out(uk, reduced)
    a.set_records_in(int(vals.sum()))
    a.check_fold()  # exact: combined readback balances the map side
    assert a.violations == []
    assert a.doc()["reduction"]["ratio"] == pytest.approx(
        keys.shape[0] / uk.shape[0], rel=1e-3)
    # drop one reduced row -> named error, violation recorded
    b = DataPlaneAudit(4)
    b.record_fold_in(keys, vals)
    b.record_fold_out(uk[:-1], reduced[:-1])
    b.set_records_in(int(vals.sum()))
    with pytest.raises(ConservationError, match="conservation violated"):
        b.check_fold()
    assert len(b.violations) == 1
    assert b.doc()["conservation"]["violations"]


def test_audit_pairs_violation_on_corruption():
    rng = np.random.default_rng(21)
    keys = rng.integers(0, 1 << 60, 1000, dtype=np.uint64)
    docs = np.arange(1000, dtype=np.int64)
    a = DataPlaneAudit(4)
    a.record_pairs_in(keys, docs)
    docs2 = docs.copy()
    docs2[500] ^= 1  # same rows, one corrupted doc id
    a.record_pairs_out(keys, docs2)
    with pytest.raises(ConservationError,
                       match="pair contents changed in flight"):
        a.check_pairs()


def test_audit_reduce_distributed_two_halves():
    """Two simulated processes: each audits half the rows; after the
    reduction the second holds the single-process oracle's global
    state and the replicated readback balances it."""
    keys = _zipf_corpus(6000, 80, seed=13)
    vals = np.ones(keys.shape[0], np.int64)
    uk, inv = np.unique(keys, return_inverse=True)
    reduced = np.bincount(inv).astype(np.int64)

    halves = np.array_split(np.arange(keys.shape[0]), 2)
    a0, a1 = DataPlaneAudit(4), DataPlaneAudit(4)
    a0.record_fold_in(keys[halves[0]], vals[halves[0]])
    a1.record_fold_in(keys[halves[1]], vals[halves[1]])
    a0.set_records_in(halves[0].shape[0])
    a1.set_records_in(halves[1].shape[0])

    # capture each side's flat vector, then hand both the same (2, k)
    flats = []
    a0.reduce_distributed(lambda v: (flats.append(v.copy()),
                                     np.stack([v, v * np.uint64(0)]))[1])
    a1.reduce_distributed(lambda v: np.stack([flats[0], v]))

    # a1 now holds the global audit; the replicated readback closes it
    a1.record_fold_out(uk, reduced)
    a1.check_fold()
    assert a1.records_in == keys.shape[0]
    oracle = np.bincount(partition_of(keys, 4), minlength=4)
    assert a1.doc()["skew"]["rows_per_partition"] == oracle.tolist()

    # a process that recorded NOTHING (it owned zero chunks) must still
    # ship the same payload shape — np.stack raises on divergence, the
    # host-side spelling of the allgather wedge this guards against
    empty = DataPlaneAudit(4)
    empty.reduce_distributed(lambda v: np.stack([flats[0], v]))
    assert empty.records_in == halves[0].shape[0]
    half_oracle = np.bincount(partition_of(keys[halves[0]], 4),
                              minlength=4)
    assert (empty.stages["map_out"].rows.astype(np.int64).tolist()
            == half_oracle.tolist())


class _DictTrackerAudit:
    """Oracle for the map-side fold-in: per-partition digests through
    ``ufunc.at``, HLL registers, and the hot-key candidates as a dict,
    updated key by key and pruned to the ``_HOT_KEEP`` largest counts by
    a stable sort whenever it holds more than ``_HOT_CAP`` keys."""

    def __init__(self, S):
        self.S = S
        for f in ("rows", "bytes", "vsum", "wsum", "xor", "sum"):
            setattr(self, f, np.zeros(S, np.uint64))
        self.regs = np.zeros(S << dpm.HLL_P, np.int32)
        self.hot: "dict[int, int]" = {}
        self.prunes = 0

    def _rows(self, keys, row_bytes):
        part = partition_of(keys, self.S)
        rows = np.bincount(part, minlength=self.S).astype(np.uint64)
        self.rows += rows
        self.bytes += rows * np.uint64(row_bytes)
        return part

    def _skew(self, keys, part, weights):
        b, r = dpm._hll_ranks(dpm.mix64(keys), dpm.HLL_P)
        np.maximum.at(self.regs, (part << dpm.HLL_P) + b, r)
        uk, inv = np.unique(keys, return_inverse=True)
        cnt = np.bincount(inv, weights=None if weights is None
                          else np.asarray(weights, np.float64))
        for k, c in zip(uk.tolist(), cnt.tolist()):
            self.hot[k] = self.hot.get(k, 0) + int(c)
        if len(self.hot) > dpm._HOT_CAP:
            self.hot = dict(self.top(dpm._HOT_KEEP))
            self.prunes += 1

    def fold_in(self, keys, values):
        part = self._rows(keys, (keys.nbytes + values.nbytes)
                          // keys.shape[0])
        v = values.astype(np.int64).astype(np.uint64)
        np.add.at(self.vsum, part, v)
        np.add.at(self.wsum, part, dpm.mix64(keys) * v)
        self._skew(keys, part, values)

    def pairs_in(self, keys, docs):
        part = self._rows(keys, 16)
        h = dpm.mix64(keys ^ dpm.mix64(docs.view(np.uint64)))
        np.bitwise_xor.at(self.xor, part, h)
        np.add.at(self.sum, part, h)
        self._skew(keys, part, None)

    def top(self, n):
        return sorted(self.hot.items(), key=lambda kv: -kv[1])[:n]

    def merge(self, other):
        """Two processes' states after the cross-process reduction:
        each ships its ``_HOT_KEEP`` top candidates, summed by key."""
        out = _DictTrackerAudit(self.S)
        for f in ("rows", "bytes", "vsum", "wsum", "sum"):
            setattr(out, f, getattr(self, f) + getattr(other, f))
        out.xor = self.xor ^ other.xor
        out.regs = np.maximum(self.regs, other.regs)
        for side in (self, other):
            for k, c in side.top(dpm._HOT_KEEP):
                if c:
                    out.hot[k] = out.hot.get(k, 0) + c
        out.prunes = self.prunes + other.prunes
        return out


class _Gauges(dict):
    def set(self, name, value):
        self[name] = value


def _zipf_chunks(n_chunks, rows, pairs, seed):
    """Zipf (s = 1.1) draws over 60,000 hashed keys, ~20k distinct a
    chunk so every chunk outgrows ``_HOT_CAP``: fold chunks combined to
    one row per key with its int32 count, pair chunks left with their
    keys repeated and a random doc per row."""
    rng = np.random.default_rng(seed)
    vocab = rng.integers(1, 1 << 63, 60_000, dtype=np.uint64)
    cdf = np.cumsum(1.0 / np.arange(1, vocab.shape[0] + 1) ** 1.1)
    cdf /= cdf[-1]
    out = []
    for _ in range(n_chunks):
        keys = vocab[np.minimum(np.searchsorted(cdf, rng.random(rows)),
                                vocab.shape[0] - 1)]
        if pairs:
            out.append((keys, rng.integers(0, 1 << 40, rows)))
        else:
            uk, cnt = np.unique(keys, return_counts=True)
            perm = rng.permutation(uk.shape[0])
            out.append((uk[perm], cnt[perm].astype(np.int32)))
    return out


@pytest.mark.parametrize("case", ["fold", "pairs", "two_halves"])
def test_hot_tracker_matches_dict_oracle(case):
    """The array candidate table against the dict tracker it replaced,
    on chunks that each take the prune path: identical digests and
    registers, the same top-k hot keys and counts, a table bounded by
    ``_HOT_CAP``, and ``data/hot_prunes`` counting the pruned chunks."""
    pairs = case == "pairs"
    chunks = _zipf_chunks(4 if case == "two_halves" else 3, 160_000,
                          pairs, seed=26)
    sides = [chunks] if case != "two_halves" else [chunks[:2], chunks[2:]]
    audits, oracles = [], []
    for side in sides:
        a, o = DataPlaneAudit(4), _DictTrackerAudit(4)
        for keys, vals in side:
            assert np.unique(keys).shape[0] > dpm._HOT_CAP
            if pairs:
                a.record_pairs_in(keys, vals)
                o.pairs_in(keys, vals)
            else:
                a.record_fold_in(keys, vals)
                o.fold_in(keys, vals)
            assert a._hot_k.shape[0] <= dpm._HOT_CAP
        assert a.hot_prunes == o.prunes == len(side)
        audits.append(a)
        oracles.append(o)
    a, o = audits[-1], oracles[-1]
    if case == "two_halves":
        flats = []
        audits[0].reduce_distributed(
            lambda v: (flats.append(v.copy()), np.stack([v, v]))[1])
        a.reduce_distributed(lambda v: np.stack([flats[0], v]))
        o = oracles[0].merge(oracles[1])
        assert a._hot_k.shape[0] <= dpm._HOT_CAP

    st = a.stages["map_out"]
    for f in ("rows", "bytes", "vsum", "wsum", "xor", "sum"):
        assert np.array_equal(getattr(st, f), getattr(o, f)), f
    assert np.array_equal(a._regs, o.regs)
    top = o.top(dpm.HOT_KEYS)
    assert a.hot_hashes() == [k for k, _ in top]
    assert a.doc()["skew"]["hot_keys"] == [
        {"hash": f"{k:#018x}", "key": None, "rows": c} for k, c in top]
    g = _Gauges()
    a.publish(g)
    assert g["data/hot_prunes"] == o.prunes == len(chunks)


def test_hot_tracker_ties_break_by_hash():
    """Equal counts rank by hash ascending, in the prune and the top-k,
    whatever order the rows arrive in."""
    rng = np.random.default_rng(4)
    keys = rng.permutation(rng.integers(1, 1 << 63, dpm._HOT_CAP + 1,
                                        dtype=np.uint64))
    heavy = keys[-3:]
    a = DataPlaneAudit(1)
    a.record_fold_in(keys, np.ones(keys.shape[0], np.int32))
    assert a.hot_prunes == 1
    assert np.array_equal(a._hot_k, np.sort(keys)[:dpm._HOT_KEEP])
    b = DataPlaneAudit(1)
    b.record_fold_in(heavy, np.full(3, 5, np.int32))
    b.record_fold_in(keys, np.ones(keys.shape[0], np.int32))
    assert b.hot_prunes == 1
    assert b.hot_hashes() == (np.sort(heavy).tolist()
                              + np.sort(keys[:-3])[:dpm.HOT_KEYS - 3].tolist())


# --- ledger gates + SLO rule ---------------------------------------------


def _entry(metrics):
    return {"workload": "wordcount", "config_hash": "h", "version": "v",
            "corpus_bytes": 1, "n_processes": 1, "phases_s": {},
            "metrics": metrics}


def test_ledger_gate_conservation_violations():
    from map_oxidize_tpu.obs.ledger import diff_entries

    d = diff_entries(_entry({"data/conservation_violations": 0}),
                     _entry({"data/conservation_violations": 1}),
                     force=True)
    assert any("row-conservation violations" in r for r in d["regressions"])
    ok = diff_entries(_entry({"data/conservation_violations": 0}),
                      _entry({"data/conservation_violations": 0}),
                      force=True)
    assert not any("conservation" in r for r in ok["regressions"])


def test_ledger_gate_imbalance_points():
    from map_oxidize_tpu.obs.ledger import (
        DATA_IMBALANCE_GATE_POINTS,
        diff_entries,
    )

    lo, hi = 1.2, 1.2 + DATA_IMBALANCE_GATE_POINTS + 0.5
    d = diff_entries(_entry({"data/imbalance_factor": lo}),
                     _entry({"data/imbalance_factor": hi}), force=True)
    assert any("key-skew regression" in r for r in d["regressions"])
    # a sub-threshold wiggle stays quiet
    ok = diff_entries(_entry({"data/imbalance_factor": lo}),
                      _entry({"data/imbalance_factor": lo + 0.3}),
                      force=True)
    assert not any("key-skew" in r for r in ok["regressions"])


def test_skew_slo_rule_has_evidence():
    from map_oxidize_tpu.obs.slo import DEFAULT_RULES, SloRule

    rules = [SloRule(**r) for r in DEFAULT_RULES]
    skew = [r for r in rules if r.name == "data-partition-skew"]
    assert len(skew) == 1
    skew[0].validate()
    assert skew[0].metric == "data/imbalance_factor"
    assert skew[0].evidence == "critpath/straggler_save_frac"


# --- end-to-end (single process) -----------------------------------------


def _write_corpus(path, lines=2000, vocab=17):
    with open(path, "w") as f:
        for i in range(lines):
            f.write(f"alpha beta gamma word{i % vocab}\n")


def test_wordcount_end_to_end_audit(tmp_path):
    from map_oxidize_tpu.config import JobConfig
    from map_oxidize_tpu.runtime.driver import run_wordcount_job
    from map_oxidize_tpu.workloads.wordcount import make_wordcount

    inp = tmp_path / "c.txt"
    _write_corpus(inp)
    mout = tmp_path / "m.json"
    cfg = JobConfig(input_path=str(inp), output_path="",
                    metrics_out=str(mout),
                    ledger_dir=str(tmp_path / "ledger"))
    mapper, reducer = make_wordcount(cfg.tokenizer, cfg.use_native)
    run_wordcount_job(cfg, mapper, reducer)

    doc = json.loads(mout.read_text())
    d = doc["data"]
    assert d["schema"] == dpm.DATA_SCHEMA
    assert d["conservation"]["violations"] == []
    assert d["conservation"]["checks"] >= 2
    st = d["stages"]
    assert (st["map_out"]["weighted_checksum"]
            == st["reduce_out"]["weighted_checksum"])
    assert st["map_out"]["value_sum"] == d["records_in"]
    g = doc["gauges"]
    assert g["data/conservation_violations"] == 0
    assert g["data/reduction_ratio"] > 0
    assert g["data/imbalance_factor"] >= 1.0
    # the acceptance gauge rides the ledger entry's flat metrics AND the
    # compact data section rides the entry itself
    entry = json.loads((tmp_path / "ledger" / "ledger.jsonl")
                       .read_text().splitlines()[-1])
    assert entry["metrics"]["data/reduction_ratio"] == pytest.approx(
        g["data/reduction_ratio"])
    assert entry["data"]["imbalance_factor"] == pytest.approx(
        g["data/imbalance_factor"])
    assert entry["data"]["violations"] == []


def test_injected_row_drop_fails_named(tmp_path, monkeypatch):
    """A single pair record dropped inside the spill round-trip must
    fail the job with ConservationError — not silently shrink output."""
    from map_oxidize_tpu.config import JobConfig
    from map_oxidize_tpu.runtime.driver import run_inverted_index_job
    from map_oxidize_tpu.runtime.spill import BucketFiles

    inp = tmp_path / "docs.txt"
    with open(inp, "w") as f:
        for i in range(300):
            f.write(f"doc{i} shared words here word{i % 11}\n")

    orig = BucketFiles.write_partitioned
    state = {"dropped": False}

    def drop_one(self, suffix, rows, counts, offs, *a, **kw):
        if not state["dropped"] and rows.shape[0] > 1:
            state["dropped"] = True
            rows = rows[:-1]
            offs = np.minimum(offs, rows.shape[0])
        return orig(self, suffix, rows, counts, offs, *a, **kw)

    monkeypatch.setattr(BucketFiles, "write_partitioned", drop_one)
    cfg = JobConfig(input_path=str(inp), output_path="",
                    collect_max_rows=400)
    with pytest.raises(ConservationError,
                       match="spill conservation violated"):
        run_inverted_index_job(cfg)
    assert state["dropped"]


def test_obs_data_cli_renders(tmp_path):
    from map_oxidize_tpu.config import JobConfig
    from map_oxidize_tpu.runtime.driver import run_inverted_index_job

    inp = tmp_path / "docs.txt"
    with open(inp, "w") as f:
        for i in range(200):
            f.write(f"doc{i} common words word{i % 7}\n")
    mout = tmp_path / "m.json"
    cfg = JobConfig(input_path=str(inp), output_path="",
                    metrics_out=str(mout))
    run_inverted_index_job(cfg)

    from map_oxidize_tpu.obs.cli import obs_main

    rc = obs_main(["data", str(mout)])
    assert rc == 0
    doc = json.loads(mout.read_text())
    text = dpm.render(doc["data"])
    assert "conservation" in text and "[OK]" in text
    assert "imbalance factor" in text
    assert "reduction ratio" in text


def test_no_data_audit_flag_disables(tmp_path):
    from map_oxidize_tpu.config import JobConfig
    from map_oxidize_tpu.runtime.driver import run_wordcount_job
    from map_oxidize_tpu.workloads.wordcount import make_wordcount

    inp = tmp_path / "c.txt"
    _write_corpus(inp, lines=200)
    mout = tmp_path / "m.json"
    cfg = JobConfig(input_path=str(inp), output_path="",
                    metrics_out=str(mout), data_audit=False)
    mapper, reducer = make_wordcount(cfg.tokenizer, cfg.use_native)
    run_wordcount_job(cfg, mapper, reducer)  # legacy check still passes
    doc = json.loads(mout.read_text())
    assert "data" not in doc
    assert not any(k.startswith("data/") for k in doc["gauges"])


# --- 2-process Gloo -------------------------------------------------------

_CHILD = r"""
import json, logging, sys
pid = int(sys.argv[1]); nproc = int(sys.argv[2]); port = sys.argv[3]
corpus = sys.argv[4]; docs = sys.argv[5]; art = sys.argv[6]
from map_oxidize_tpu.config import JobConfig
from map_oxidize_tpu.utils.logging import configure
from map_oxidize_tpu.parallel.distributed import (
    init_distributed, run_distributed_job)
configure(logging.INFO)
init_distributed(f"127.0.0.1:{port}", num_processes=nproc, process_id=pid)
common = dict(output_path="", chunk_bytes=4096, batch_size=1 << 12,
              key_capacity=1 << 12, top_k=5, metrics=False,
              dist_coordinator=f"127.0.0.1:{port}",
              dist_num_processes=nproc, dist_process_id=pid)
cfg = JobConfig(input_path=corpus, metrics_out=f"{art}/wc.json",
                ledger_dir=f"{art}/ledger", **common)
r = run_distributed_job(cfg, "wordcount")
cfg2 = JobConfig(input_path=docs, metrics_out=f"{art}/ii.json",
                 collect_max_rows=512, **common)
r2 = run_distributed_job(cfg2, "invertedindex")
print("RESULT", json.dumps({"records": r.records, "n_keys": r.n_keys,
                            "pairs": r2.n_pairs}))
"""


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _env():
    env = dict(os.environ)
    for k in ("PJRT_LIBRARY_PATH",
              "TPU_LIBRARY_PATH", "PJRT_DEVICE", "TPU_ACCELERATOR_TYPE",
              "TPU_TOPOLOGY", "TPU_WORKER_HOSTNAMES"):
        env.pop(k, None)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    return env


@pytest.fixture(scope="module")
def dist_dataplane_run(tmp_path_factory):
    """One 2-process pair running a SKEWED wordcount then a forced-spill
    inverted index; returns the artifact dir and stdout logs."""
    tmp = tmp_path_factory.mktemp("dist_data")
    corpus = tmp / "c.txt"
    rng = np.random.default_rng(4)
    with open(corpus, "wb") as f:
        for _ in range(2500):
            tail = b" ".join(b"w%d" % i for i in rng.integers(0, 40, 3))
            f.write(b"hot hot hot " + tail + b"\n")
    docs = tmp / "d.txt"
    with open(docs, "wb") as f:
        for i in range(600):
            f.write(b"doc%d shared words plus w%d\n" % (i, i % 19))
    env = _env()
    logs = None
    for attempt in range(2):  # free-port probe is inherently racy
        port = _free_port()
        procs = [subprocess.Popen(
            [sys.executable, "-c", _CHILD, str(i), "2", str(port),
             str(corpus), str(docs), str(tmp)],
            env=env, cwd=REPO, stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT, text=True) for i in range(2)]
        logs = []
        for p in procs:
            try:
                out, _ = p.communicate(timeout=420)
            except subprocess.TimeoutExpired:
                for q in procs:
                    q.kill()
                out = "(timeout)"
            logs.append(out)
        if all(p.returncode == 0 for p in procs):
            break
        if attempt == 1:
            for i, p in enumerate(procs):
                assert p.returncode == 0, f"process {i} failed:\n{logs[i]}"
    return tmp, logs


def test_distributed_fold_audit(dist_dataplane_run):
    tmp, _logs = dist_dataplane_run
    docs = [json.loads((tmp / f"wc.json.proc{p}").read_text())
            for p in (0, 1)]
    for m in docs:
        d = m["data"]
        assert d["conservation"]["violations"] == []
        st = m["data"]["stages"]
        # the checksum matches ACROSS the exchange: locally-recorded map
        # outputs, allgather-reduced, equal the replicated readback
        assert (st["map_out"]["weighted_checksum"]
                == st["reduce_out"]["weighted_checksum"])
        assert st["map_out"]["value_sum"] == st["reduce_out"]["value_sum"]
        assert d["skew"]["imbalance_factor"] >= 1.0
        assert d["reduction"]["ratio"] > 1.0  # 'hot' repeats per line
        assert m["gauges"]["data/conservation_violations"] == 0
    # the reduced audit is replicated: both processes publish the SAME
    # global figures (records_in, per-partition rows, checksums)
    assert docs[0]["data"]["records_in"] == docs[1]["data"]["records_in"]
    assert (docs[0]["data"]["skew"]["rows_per_partition"]
            == docs[1]["data"]["skew"]["rows_per_partition"])
    assert (docs[0]["data"]["stages"]["map_out"]["weighted_checksum"]
            == docs[1]["data"]["stages"]["map_out"]["weighted_checksum"])
    # the hot key dominates and resolves to its string on both processes
    for m in docs:
        hot = m["data"]["skew"]["hot_keys"][0]
        assert hot["key"] == "hot"
    # process 0's ledger entry carries the acceptance gauge + section
    entry = json.loads((tmp / "ledger" / "ledger.jsonl")
                       .read_text().splitlines()[-1])
    assert entry["metrics"]["data/reduction_ratio"] > 1.0
    assert entry["data"]["violations"] == []


def test_distributed_spilled_pairs_audit(dist_dataplane_run):
    tmp, logs = dist_dataplane_run
    docs = [json.loads((tmp / f"ii.json.proc{p}").read_text())
            for p in (0, 1)]
    for m in docs:
        d = m["data"]
        assert d["conservation"]["violations"] == []
        st = d["stages"]
        assert st["map_out"]["rows"] == st["reduce_out"]["rows"]
        assert st["map_out"]["pair_xor"] == st["reduce_out"]["pair_xor"]
        assert st["map_out"]["pair_sum"] == st["reduce_out"]["pair_sum"]
        # the forced spill actually happened, and its round-trip digests
        # balanced (a mismatch would have aborted the child)
        assert m["counters"].get("spill/rows", 0) > 0
    assert (docs[0]["data"]["stages"]["map_out"]["pair_xor"]
            == docs[1]["data"]["stages"]["map_out"]["pair_xor"])

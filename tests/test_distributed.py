"""Multi-host execution: real OS processes, one global 8-device CPU mesh,
Gloo collectives over the coordination service — the DCN path SURVEY §2
promises, without pod hardware.

Each process maps its chunk subset, the lockstep feed assembles global
batches with make_array_from_process_local_data, the all_to_all exchange
routes keys across the process boundary, and every process must read back
identical, oracle-exact results — including winner STRINGS, gathered
through the mesh (no shared state outside the collectives).
"""

import json
import os
import socket
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_CHILD = r"""
import json, os, sys
pid = int(sys.argv[1]); nproc = int(sys.argv[2]); port = sys.argv[3]
corpus = sys.argv[4]; out_path = sys.argv[5]; workload = sys.argv[6]
ckpt = sys.argv[7] if len(sys.argv) > 7 and sys.argv[7] != "-" else None
final = sys.argv[8] if len(sys.argv) > 8 and sys.argv[8] != "-" else ""
precision = "highest"
if workload == "kmeans_bf16":  # kmeans with the bf16 storage/matmul mode
    workload, precision = "kmeans", "bf16"
kmeans_resume = workload == "kmeans_resume"
if kmeans_resume:
    workload = "kmeans"
from map_oxidize_tpu.config import JobConfig
from map_oxidize_tpu.parallel.distributed import (
    init_distributed, run_distributed_job)
init_distributed(f"127.0.0.1:{port}", num_processes=nproc, process_id=pid)

die_after = int(os.environ.get("_MOXT_TEST_DIE_AFTER_CHUNKS", "0"))
if die_after and pid == 1:
    # deterministic mid-run failure: die after N checkpoint saves (the
    # spilled prefix must survive and resume)
    from map_oxidize_tpu.runtime.checkpoint import CheckpointStore
    orig = CheckpointStore.save
    state = {"n": 0}
    def dying_save(self, idx, out, next_offset):
        orig(self, idx, out, next_offset)
        state["n"] += 1
        if state["n"] >= die_after:
            os._exit(3)
    CheckpointStore.save = dying_save

cfg = JobConfig(input_path=corpus, output_path=final, chunk_bytes=4096,
                batch_size=1 << 12, key_capacity=1 << 12, top_k=5,
                metrics=False, checkpoint_dir=ckpt,
                keep_intermediates=bool(ckpt),
                kmeans_k=4, kmeans_iters=3, kmeans_precision=precision)
if kmeans_resume:
    # interrupted-training shape: 2 iterations snapshot (kept), then a
    # 3-iteration run resumes the snapshot and runs only the last one
    import dataclasses
    run_distributed_job(dataclasses.replace(
        cfg, kmeans_iters=2, keep_intermediates=True), "kmeans")
    cfg = dataclasses.replace(cfg, keep_intermediates=False)
r = run_distributed_job(cfg, workload)
payload = {
    "n_keys": r.n_keys, "n_pairs": r.n_pairs, "records": r.records,
    "estimate": r.estimate, "flag_rounds": r.flag_rounds,
    "resumed": r.resumed_chunks,
    "top": [[f"{h:#018x}",
             None if w is None else w.decode("utf-8"), c]
            for h, w, c in r.top],
    "counts": {str(k): v for k, v in (r.counts or {}).items()},
    "centroids": None if r.centroids is None else r.centroids.tolist(),
    "resumed_iters": (r.metrics or {}).get("resumed_iters", 0),
}
with open(out_path, "w") as f:
    json.dump(payload, f, sort_keys=True)
print("child", pid, "ok")
"""


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _write_corpus(path, lines=3000, seed=11):
    rng = np.random.default_rng(seed)
    words = [b"Alpha", b"beta,", b"Gamma.", b"delta", b"eps;", b"zeta"]
    with open(path, "wb") as f:
        for _ in range(lines):
            f.write(b" ".join(words[int(i)]
                              for i in rng.integers(0, 6, 6)) + b"\n")


def _env(devices: int):
    env = dict(os.environ)
    for k in ("PJRT_LIBRARY_PATH",
              "TPU_LIBRARY_PATH", "PJRT_DEVICE", "TPU_ACCELERATOR_TYPE",
              "TPU_TOPOLOGY", "TPU_WORKER_HOSTNAMES", "_MOXT_DRYRUN_CHILD"):
        env.pop(k, None)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    return env


def _launch(tmp_path, corpus, nproc, workload, devices=None, ckpt=None,
            extra_env=None, expect_fail=False, timeout=420, final=None):
    """Run ``nproc`` child processes; returns (payload list, logs).  The
    free-port probe is inherently racy (bind/close/reuse), so the whole
    launch retries once on a fresh port.  ``devices`` is the PER-PROCESS
    local device count; the global mesh is nproc times that (default: an
    8-device global mesh regardless of process count)."""
    env = _env(devices if devices is not None else 8 // nproc)
    if extra_env:
        env.update(extra_env)
    outs = [str(tmp_path / f"out_{workload}_{i}.json") for i in range(nproc)]
    for attempt in range(2):
        port = _free_port()
        procs = [subprocess.Popen(
            [sys.executable, "-c", _CHILD, str(i), str(nproc), str(port),
             str(corpus), outs[i], workload, ckpt or "-", final or "-"],
            env=env, cwd=REPO, stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT, text=True) for i in range(nproc)]
        logs = []
        for p in procs:
            try:
                out, _ = p.communicate(timeout=timeout)
            except subprocess.TimeoutExpired:
                for q in procs:
                    q.kill()
                out = "(timeout)"
            logs.append(out)
        if expect_fail:
            return [p.returncode for p in procs], logs
        if all(p.returncode == 0 for p in procs):
            break
        if attempt == 1:
            for i, p in enumerate(procs):
                assert p.returncode == 0, f"process {i} failed:\n{logs[i]}"
    results = []
    for path in outs:
        with open(path) as f:
            results.append(json.load(f))
    return results, logs


def _wordcount_oracle(corpus):
    from map_oxidize_tpu.ops.hashing import moxt64_bytes
    from map_oxidize_tpu.workloads.reference_model import wordcount_model

    with open(corpus, "rb") as f:
        model = wordcount_model([f.read()])
    return model, {moxt64_bytes(w): c for w, c in model.items()}


@pytest.mark.parametrize("nproc,devices", [(2, 4), (4, 2)])
def test_multiprocess_wordcount_matches_oracle(tmp_path, nproc, devices):
    corpus = tmp_path / "c.txt"
    _write_corpus(corpus)
    results, _ = _launch(tmp_path, corpus, nproc, "wordcount",
                         devices=devices)
    model, want = _wordcount_oracle(corpus)

    # every process sees the SAME replicated result; `records` is local
    # (this process's mapped share) and must SUM to the corpus total
    local = [r.pop("records") for r in results]
    assert sum(local) == sum(model.values())
    for r in results[1:]:
        assert r == results[0]
    got = {int(k): v for k, v in results[0]["counts"].items()}
    assert got == want
    # top-k: counts match the oracle head AND the winner STRINGS are
    # resolved across processes (each word's bytes live in only some
    # processes' dictionaries)
    want_top = sorted(model.items(), key=lambda kv: (-kv[1], kv[0]))[:5]
    got_counts = [c for _h, _w, c in results[0]["top"]]
    assert got_counts == [c for _w, c in want_top]
    got_words = {w for _h, w, _c in results[0]["top"]}
    assert got_words == {w.decode() for w, _c in want_top}
    assert results[0]["flag_rounds"] >= 1


def test_two_process_invertedindex_matches_oracle(tmp_path):
    corpus = tmp_path / "ii.txt"
    _write_corpus(corpus, lines=1500)
    results, _ = _launch(tmp_path, corpus, 2, "invertedindex")
    from map_oxidize_tpu.workloads.inverted_index import inverted_index_model

    model = inverted_index_model(str(corpus))
    for r in results:
        r.pop("records")
    assert results[0] == results[1]
    assert results[0]["n_keys"] == len(model)
    assert results[0]["n_pairs"] == sum(len(d) for d in model.values())
    # tie-break is hash-ascending (the engine convention), so compare the
    # df sequence and each winner's correctness rather than exact order
    want_dfs = sorted((len(d) for d in model.values()), reverse=True)[:5]
    assert [c for _h, w, c in results[0]["top"]] == want_dfs
    for _h, w, c in results[0]["top"]:
        assert w is not None and len(model[w.encode()]) == c


def test_two_process_distinct_estimate(tmp_path):
    corpus = tmp_path / "d.txt"
    _write_corpus(corpus, lines=800)
    results, _ = _launch(tmp_path, corpus, 2, "distinct")
    for r in results:
        r.pop("records")
    assert results[0] == results[1]
    # 6-word vocab: HLL's linear-counting regime is near-exact
    assert abs(results[0]["estimate"] - 6) < 0.5


def test_two_process_checkpoint_resume(tmp_path):
    """Process 1 dies after spilling 2 chunks; the re-run resumes its
    spilled prefix (resumed > 0 on process 1) and the result is still
    oracle-exact."""
    corpus = tmp_path / "ck.txt"
    _write_corpus(corpus)
    ckpt = str(tmp_path / "ckpt")

    rcs, logs = _launch(tmp_path, corpus, 2, "wordcount", ckpt=ckpt,
                        extra_env={"_MOXT_TEST_DIE_AFTER_CHUNKS": "2"},
                        expect_fail=True, timeout=180)
    assert any(rc != 0 for rc in rcs), f"expected a failed first run: {logs}"
    # the dead process's spill survived
    assert os.path.isdir(os.path.join(ckpt, "proc_1"))

    results, _ = _launch(tmp_path, corpus, 2, "wordcount", ckpt=ckpt)
    _model, want = _wordcount_oracle(corpus)
    resumed = [r.pop("resumed") for r in results]
    for r in results:
        r.pop("records")
    assert results[0] == results[1]
    got = {int(k): v for k, v in results[0]["counts"].items()}
    assert got == want
    assert resumed[1] >= 2  # process 1 replayed its spilled prefix


def test_process_death_aborts_cleanly(tmp_path):
    """A process dying mid-run must produce a clean nonzero abort on the
    survivor (coordination-service heartbeat / collective failure), not a
    hang past the test timeout."""
    corpus = tmp_path / "dd.txt"
    _write_corpus(corpus)
    # no checkpoint dir: _MOXT_TEST_DIE_AFTER_CHUNKS needs one to count
    # saves, so use it WITH a ckpt dir but assert on process 0's fate
    ckpt = str(tmp_path / "ck2")
    rcs, logs = _launch(tmp_path, corpus, 2, "wordcount", ckpt=ckpt,
                        extra_env={"_MOXT_TEST_DIE_AFTER_CHUNKS": "1"},
                        expect_fail=True, timeout=240)
    assert rcs[1] != 0  # the deliberate death
    # the survivor must EXIT (nonzero), not hang: a timeout above would
    # have killed it and left "(timeout)" in its log
    assert rcs[0] is not None and rcs[0] != 0, f"survivor: {logs[0]}"
    assert "(timeout)" not in logs[0], (
        "survivor hung past the collective timeout instead of aborting:\n"
        + logs[0][-2000:])


def test_gather_strings_single_process():
    """Collective semantics degrade to identity in a single process:
    known hashes resolve, unknown hashes are absent, empty input is
    empty.  (Cross-process resolution is covered by the word-top tests
    above — each word's bytes live in only some processes.)"""
    from map_oxidize_tpu.ops.hashing import HashDictionary, moxt64_bytes
    from map_oxidize_tpu.parallel.distributed import gather_strings

    d = HashDictionary()
    h1, h2 = moxt64_bytes(b"alpha"), moxt64_bytes(b"beta")
    d.add(h1, b"alpha")
    got = gather_strings([h1, h2], d)
    assert got == {h1: b"alpha"}
    assert gather_strings([], d) == {}


def test_two_process_output_byte_identical_to_single(tmp_path):
    """--output parity (the reference's primary artifact,
    main.rs:170-182): a 2-process run writes per-partition shard files
    whose concatenated, sorted rows are byte-identical to the
    single-process final_result.txt — for wordcount AND invertedindex;
    the distributed distinct file (written once, registers replicated)
    must equal the single-process file outright."""
    from map_oxidize_tpu.config import JobConfig
    from map_oxidize_tpu.runtime import run_job

    corpus = tmp_path / "po.txt"
    _write_corpus(corpus, lines=1200)

    def single(workload, out):
        run_job(JobConfig(input_path=str(corpus), output_path=str(out),
                          backend="cpu", num_shards=1, metrics=False,
                          chunk_bytes=4096), workload)
        return out.read_bytes()

    def parts(workload, out):
        _launch(tmp_path, corpus, 2, workload, final=str(out))
        shard_files = sorted(tmp_path.glob(out.name + ".part*"))
        assert [p.name for p in shard_files] == [
            out.name + ".part0of2", out.name + ".part1of2"]
        rows = []
        for p in shard_files:
            rows.extend(p.read_bytes().splitlines(keepends=True))
        return b"".join(sorted(rows))

    assert (parts("wordcount", tmp_path / "wc.txt")
            == single("wordcount", tmp_path / "wc_single.txt"))
    assert (parts("invertedindex", tmp_path / "ii.txt")
            == single("invertedindex", tmp_path / "ii_single.txt"))

    # wide-vocab corpus: most words live in only ONE process's chunks, so
    # partition resolution MUST go through the cross-process miss gather
    # (the 6-word corpus above resolves everything locally and would hide
    # a broken gather — it did in round 5: 64-bit hashes shipped as int64
    # were silently truncated to int32 by process_allgather)
    wide = tmp_path / "wide.txt"
    with open(wide, "wb") as f:
        for i in range(3000):
            f.write(b"unique%05d shared\n" % i)
    corpus = wide
    assert (parts("wordcount", tmp_path / "ww.txt")
            == single("wordcount", tmp_path / "ww_single.txt"))

    _launch(tmp_path, corpus, 2, "distinct", final=str(tmp_path / "d.txt"))
    assert ((tmp_path / "d.txt").read_bytes()
            == single("distinct", tmp_path / "d_single.txt"))


def test_two_process_kmeans_matches_single_controller(tmp_path):
    """Distributed k-means (the last multi-process carve-out, removed
    round 5): 2 Gloo processes × 4 local devices run the SAME jitted psum
    iteration as the single-controller 8-shard fit.  The two processes
    must agree BITWISE (one replicated result); against the
    single-controller run the Gloo allreduce sums shards in a different
    order, so the comparison is ulp-tight (measured: 1 ulp, ~1.2e-7) but
    not exact — float addition is not associative across collective
    topologies.  The oracle comparison uses the usual float tolerance,
    and process 0's --output file carries the replicated result."""
    rng = np.random.default_rng(5)
    pts = rng.normal(size=(1000, 8)).astype(np.float32)
    path = tmp_path / "pts.npy"
    np.save(path, pts)
    out = tmp_path / "cent.npy"
    results, _ = _launch(tmp_path, path, 2, "kmeans", final=str(out))
    got = [np.array(r["centroids"], np.float32) for r in results]
    np.testing.assert_array_equal(got[0], got[1])

    from map_oxidize_tpu.parallel.kmeans import kmeans_fit_sharded
    from map_oxidize_tpu.workloads.kmeans import kmeans_model

    single = kmeans_fit_sharded(pts, pts[:4].copy(), iters=3,
                                num_shards=8, backend="cpu").centroids
    np.testing.assert_allclose(got[0], single, rtol=2e-6, atol=2e-7)
    want = pts[:4].copy()
    for _ in range(3):
        want = kmeans_model(pts, want)
    np.testing.assert_allclose(got[0], want, rtol=1e-3, atol=1e-3)
    np.testing.assert_array_equal(np.load(out), got[0])


def test_two_process_kmeans_checkpoint_resume(tmp_path):
    """Distributed k-means checkpoint/resume (the last 'no effect on
    distributed kmeans' carve-out, removed this round): a 2-iteration run
    snapshots per iteration through process 0 (kept), then a 3-iteration
    run resumes the snapshot on BOTH processes and runs only the final
    iteration.  The resumed trajectory must match the straight 3-iteration
    single-controller fit within collective-order tolerance, both
    processes must agree bitwise, and the metrics must record the resume
    (resumed_iters == 2)."""
    rng = np.random.default_rng(7)
    pts = rng.normal(size=(1000, 8)).astype(np.float32)
    path = tmp_path / "pr.npy"
    np.save(path, pts)
    ckpt = str(tmp_path / "kckpt")
    results, _ = _launch(tmp_path, path, 2, "kmeans_resume", ckpt=ckpt)
    got = [np.array(r["centroids"], np.float32) for r in results]
    np.testing.assert_array_equal(got[0], got[1])
    assert [r["resumed_iters"] for r in results] == [2, 2]

    from map_oxidize_tpu.parallel.kmeans import kmeans_fit_sharded

    single = kmeans_fit_sharded(pts, pts[:4].copy(), iters=3,
                                num_shards=8, backend="cpu").centroids
    np.testing.assert_allclose(got[0], single, rtol=2e-6, atol=2e-7)


def test_two_process_kmeans_bf16_matches_sharded(tmp_path):
    """The bf16 storage/matmul mode through the multi-process path: local
    row blocks cast to ml_dtypes.bfloat16 before assembly must produce
    the same (replicated, bitwise-identical across processes) centroids
    as the single-controller sharded bf16 fit within collective-order
    tolerance — the same numerics family, so drift stays at the ulp
    level, NOT the bf16 rounding bound."""
    rng = np.random.default_rng(6)
    centers = rng.normal(0, 10, size=(4, 8)).astype(np.float32)
    pts = (centers[rng.integers(0, 4, 900)]
           + rng.normal(0, 0.5, size=(900, 8))).astype(np.float32)
    pts[:4] = centers
    path = tmp_path / "pb.npy"
    np.save(path, pts)
    results, _ = _launch(tmp_path, path, 2, "kmeans_bf16")
    got = [np.array(r["centroids"], np.float32) for r in results]
    np.testing.assert_array_equal(got[0], got[1])

    from map_oxidize_tpu.parallel.kmeans import kmeans_fit_sharded

    single = kmeans_fit_sharded(pts, pts[:4].copy(), iters=3,
                                num_shards=8, backend="cpu",
                                precision="bf16").centroids
    np.testing.assert_allclose(got[0], single, rtol=2e-5, atol=2e-5)

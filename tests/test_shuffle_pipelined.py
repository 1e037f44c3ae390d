"""Pipelined push shuffle + remote-staged transport (PR: push shuffle).

The claims under test mirror the transports' two legs:

* **pipelined** — a placement twin of hybrid with an eager-push cadence:
  byte-parity against ``hbm``/``disk`` on the 8-virtual-device mesh, the
  map-side combiner changes row counts but never results (conservation
  checksums are sum-combine-invariant), and a 2-process Gloo run keeps
  the lockstep flag sequence consistent while pushing per-block rounds.
* **remote** — shuffle partitions that outlive a worker: a 2-process
  shared-filesystem job completes with clean-run parity after one
  process is SIGKILLed mid-shuffle (manifest prefix + claim + re-map).
"""

import json
import os
import socket
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from map_oxidize_tpu.config import JobConfig
from map_oxidize_tpu.runtime import run_job

import test_distributed as td


def _corpus(tmp_path, lines=1200):
    path = tmp_path / "c.txt"
    td._write_corpus(path, lines=lines)
    return path


# --- admit() state machines (the PUSHING state) ----------------------------


def test_pipelined_admit_state_machine():
    from map_oxidize_tpu.shuffle import make_transport

    t = make_transport("pipelined")
    assert t.name == "pipelined"
    # PUSHING while under the cap: resident placement, eager cadence
    assert t.admit(10, 100, "t") == "push"
    assert t.admit(100, 100, "t") == "push"    # cap is inclusive
    assert t.admit(101, 100, "t") == "demote"  # the one-way trip
    assert t.admit(5, 100, "t") == "spill"     # never pushes again
    assert t.spilled_state


def test_remote_admit_state_machine():
    from map_oxidize_tpu.shuffle import make_transport

    t = make_transport("remote")
    assert t.name == "remote"
    assert t.spilled_state  # SPILLED from the first row, like disk
    assert t.admit(0, 1 << 30, "t") == "spill"


# --- map-side combiner units ------------------------------------------------


def _raw_output(keys, vals=None, planes=True):
    from map_oxidize_tpu.api import MapOutput
    from map_oxidize_tpu.ops.hashing import HashDictionary

    out = MapOutput(hi=None, lo=None,
                    values=None if vals is None
                    else np.asarray(vals, np.int32),
                    dictionary=HashDictionary(),
                    records_in=len(keys),
                    keys64=np.asarray(keys, np.uint64))
    if planes:  # ensure_planes materializes implicit-ones values too
        out.ensure_planes()
    return out


def test_combine_map_output_sum():
    from map_oxidize_tpu.ops.hashing import join_u64
    from map_oxidize_tpu.shuffle import combine_map_output

    out = _raw_output([7, 3, 7, 7, 3, 9], [1, 2, 3, 4, 5, 6])
    combined, n_in, n_out = combine_map_output(out, "sum")
    assert (n_in, n_out) == (6, 3)
    k64 = join_u64(combined.hi, combined.lo)
    got = dict(zip(k64.tolist(), np.asarray(combined.values).tolist()))
    assert got == {3: 7, 7: 8, 9: 6}
    # record accounting is untouched: combining changes rows, not records
    assert combined.records_in == out.records_in


def test_combine_map_output_implicit_ones_and_minmax():
    from map_oxidize_tpu.ops.hashing import join_u64
    from map_oxidize_tpu.shuffle import combine_map_output

    out = _raw_output([5, 5, 5, 2])  # values=None -> implicit ones
    combined, n_in, n_out = combine_map_output(out, "sum")
    assert (n_in, n_out) == (4, 2)
    k64 = join_u64(combined.hi, combined.lo)
    got = dict(zip(k64.tolist(), np.asarray(combined.values).tolist()))
    assert got == {5: 3, 2: 1}
    with pytest.raises(ValueError, match="sum"):
        combine_map_output(_raw_output([1, 1], planes=False), "min")
    cm, _, _ = combine_map_output(
        _raw_output([4, 4, 8], [9, 2, 5]), "min")
    k64 = join_u64(cm.hi, cm.lo)
    assert dict(zip(k64.tolist(),
                    np.asarray(cm.values).tolist())) == {4: 2, 8: 5}
    with pytest.raises(ValueError, match="combiner supports"):
        combine_map_output(_raw_output([1], [1]), "mean")


def test_combine_identity_window_passes_through():
    from map_oxidize_tpu.shuffle import combine_map_output

    out = _raw_output([1, 2, 3], [4, 5, 6])
    combined, n_in, n_out = combine_map_output(out, "sum")
    assert combined is out and n_in == n_out == 3


def test_combine_preserves_weighted_checksum():
    """The PR 16 conservation identity is sum-combine-invariant by
    construction: sum(mix64(k) * v) mod 2^64 is unchanged when duplicate
    keys collapse into summed partials — the reason audits stay green
    with the combiner on."""
    from map_oxidize_tpu.obs.dataplane import mix64
    from map_oxidize_tpu.ops.hashing import join_u64
    from map_oxidize_tpu.shuffle import combine_map_output

    rng = np.random.default_rng(3)
    keys = rng.integers(0, 50, 4000).astype(np.uint64)
    vals = rng.integers(1, 9, 4000).astype(np.int64)

    def wsum(k, v):
        return int((mix64(np.asarray(k, np.uint64))
                    * np.asarray(v, np.int64).view(np.uint64))
                   .sum(dtype=np.uint64))

    out = _raw_output(keys, vals)
    combined, n_in, n_out = combine_map_output(out, "sum")
    assert n_out < n_in
    k64 = join_u64(combined.hi, combined.lo)
    assert wsum(keys, vals) == wsum(k64, np.asarray(combined.values))


# --- single-controller parity on the 8-virtual-device mesh ------------------


def _run_wc(corpus, out, transport, push_combine="auto"):
    cfg = JobConfig(input_path=str(corpus), output_path=str(out),
                    backend="cpu", metrics=False, chunk_bytes=4096,
                    batch_size=1 << 12, key_capacity=1 << 12,
                    shuffle_transport=transport,
                    push_combine=push_combine)
    return run_job(cfg, "wordcount")


def test_pipelined_byte_parity_vs_hbm_and_disk(tmp_path):
    """Transport swap parity: the push cadence + combiner change WHEN
    rows travel and how many, never what they add up to."""
    corpus = _corpus(tmp_path)
    r_hbm = _run_wc(corpus, tmp_path / "hbm.txt", "hbm")
    r_pipe = _run_wc(corpus, tmp_path / "pipe.txt", "pipelined")
    r_off = _run_wc(corpus, tmp_path / "off.txt", "pipelined",
                    push_combine="off")
    assert ((tmp_path / "hbm.txt").read_bytes()
            == (tmp_path / "pipe.txt").read_bytes()
            == (tmp_path / "off.txt").read_bytes())
    assert r_pipe.metrics["shuffle/transport"] == "pipelined"
    assert r_pipe.metrics["plan/shuffle_transport"] == "pipelined"
    assert r_pipe.metrics["plan/shuffle_transport_provenance"] == "pinned"
    # the push pipeline ran and published its overlap gauge
    assert "pipeline/shuffle_overlap_ratio" in r_pipe.metrics
    assert r_pipe.metrics["pipeline/shuffle_overlap_ratio"] >= 0.0
    # combiner off: no combine evidence
    assert "shuffle/push_combined_in" not in r_off.metrics
    assert dict(r_hbm.counts) == dict(r_pipe.counts)


def test_pipelined_invertedindex_parity(tmp_path):
    """Pair mode (no combiner) under the pipelined transport: placement
    is hybrid's, output is byte-identical to hbm on the 8-device mesh."""
    cfgkw = dict(backend="cpu", metrics=False, chunk_bytes=4096,
                 batch_size=1 << 12)
    corpus = _corpus(tmp_path)
    run_job(JobConfig(input_path=str(corpus),
                      output_path=str(tmp_path / "hbm.txt"),
                      shuffle_transport="hbm", **cfgkw), "invertedindex")
    r = run_job(JobConfig(input_path=str(corpus),
                          output_path=str(tmp_path / "pipe.txt"),
                          shuffle_transport="pipelined", **cfgkw),
                "invertedindex")
    assert ((tmp_path / "hbm.txt").read_bytes()
            == (tmp_path / "pipe.txt").read_bytes())
    assert r.metrics["shuffle/transport"] == "pipelined"


def test_remote_single_controller_behaves_like_disk(tmp_path):
    """Placement-wise remote IS disk on a single controller (the staged
    object layout only exists multi-process): byte parity, spill path."""
    cfgkw = dict(backend="cpu", metrics=False, chunk_bytes=4096,
                 batch_size=1 << 12)
    corpus = _corpus(tmp_path)
    run_job(JobConfig(input_path=str(corpus),
                      output_path=str(tmp_path / "disk.txt"),
                      shuffle_transport="disk", **cfgkw), "invertedindex")
    r = run_job(JobConfig(input_path=str(corpus),
                          output_path=str(tmp_path / "rem.txt"),
                          shuffle_transport="remote", **cfgkw),
                "invertedindex")
    assert ((tmp_path / "disk.txt").read_bytes()
            == (tmp_path / "rem.txt").read_bytes())
    assert r.metrics["shuffle/transport"] == "remote"


def test_combiner_conservation_audit_green(tmp_path):
    """run_job raises ConservationError on any audit violation, so a
    clean return with the combiner forced ON and a reduced feed is the
    end-to-end invariance claim."""
    corpus = _corpus(tmp_path)
    r_on = _run_wc(corpus, tmp_path / "on.txt", "pipelined",
                   push_combine="on")
    r_off = _run_wc(corpus, tmp_path / "off.txt", "pipelined",
                    push_combine="off")
    assert dict(r_on.counts) == dict(r_off.counts)
    assert ((tmp_path / "on.txt").read_bytes()
            == (tmp_path / "off.txt").read_bytes())


# --- 2-process Gloo: push-round lockstep + remote-staged recovery ----------


_PUSH_CHILD = textwrap.dedent("""
    import json, sys
    pid, port, transport, corpus, out_path = (
        int(sys.argv[1]), sys.argv[2], sys.argv[3], sys.argv[4],
        sys.argv[5])
    from map_oxidize_tpu.config import JobConfig
    from map_oxidize_tpu.parallel.distributed import (
        init_distributed, run_distributed_job)
    init_distributed(f"127.0.0.1:{port}", num_processes=2, process_id=pid)
    cfg = JobConfig(input_path=corpus, chunk_bytes=1024,
                    batch_size=1 << 12, key_capacity=1 << 12, top_k=5,
                    metrics=False, shuffle_transport=transport)
    r = run_distributed_job(cfg, "wordcount")
    payload = {
        "counts": {str(k): v for k, v in r.counts.items()},
        "flag_rounds": r.flag_rounds,
        "metrics": {k: v for k, v in (r.metrics or {}).items()
                    if str(k).startswith(("shuffle/", "pipeline/"))},
    }
    json.dump(payload, open(out_path, "w"), sort_keys=True)
    print("child", pid, "ok")
""")


def _launch_push(tmp_path, corpus, transport):
    env = td._env(4)
    outs = [str(tmp_path / f"push_{transport}_{i}.json") for i in range(2)]
    port = td._free_port()
    procs = [subprocess.Popen(
        [sys.executable, "-c", _PUSH_CHILD, str(i), str(port), transport,
         str(corpus), outs[i]],
        env=env, cwd=td.REPO, stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT, text=True) for i in range(2)]
    logs = [p.communicate(timeout=420)[0] for p in procs]
    assert all(p.returncode == 0 for p in procs), "\n".join(logs)
    return [json.load(open(o)) for o in outs]


def test_push_round_lockstep_consistency_2proc(tmp_path):
    """Both processes run the same flag-round sequence under the push
    cadence, agree on the replicated counts, and match the barrier
    transport's results exactly — while the push evidence (rounds, rows,
    window-combine reduction, overlap gauge) is live."""
    corpus = tmp_path / "c.txt"
    td._write_corpus(corpus, lines=600)
    base = _launch_push(tmp_path, corpus, "hbm")
    push = _launch_push(tmp_path, corpus, "pipelined")
    assert push[0]["counts"] == push[1]["counts"] == base[0]["counts"]
    assert push[0]["flag_rounds"] == push[1]["flag_rounds"]
    for doc in push:
        m = doc["metrics"]
        assert m["shuffle/transport"] == "pipelined"
        assert m["shuffle/push_rounds"] >= 1
        assert m["shuffle/push_rows"] >= 1
        # the window combiner collapsed duplicate keys before the push
        assert m["shuffle/push_combined_out"] < m["shuffle/push_combined_in"]
        assert m["pipeline/shuffle_overlap_ratio"] >= 0.0


_REMOTE_CHILD = textwrap.dedent("""
    import json, os, signal, sys
    pid, corpus, outdir, die = (int(sys.argv[1]), sys.argv[2],
                                sys.argv[3], int(sys.argv[4]))
    from map_oxidize_tpu.config import JobConfig
    from map_oxidize_tpu.parallel.distributed import run_distributed_job
    from map_oxidize_tpu.shuffle import remote as rmod
    if die and pid == 1:
        # a REAL SIGKILL mid-shuffle, deterministically placed: after
        # the second committed chunk, between commit and the next append
        orig = rmod.RemoteStage.append_chunk
        n = [0]
        def bomb(self, *a, **kw):
            orig(self, *a, **kw)
            n[0] += 1
            if n[0] >= 2:
                os.kill(os.getpid(), signal.SIGKILL)
        rmod.RemoteStage.append_chunk = bomb
    cfg = JobConfig(input_path=corpus,
                    output_path=os.path.join(outdir, "out.txt"),
                    chunk_bytes=512, shuffle_transport="remote",
                    remote_stage_dir=os.path.join(outdir, "stage"),
                    remote_stage_timeout_s=8.0,
                    dist_num_processes=2, dist_process_id=pid,
                    metrics=False, progress=True)  # beats per staged chunk
    r = run_distributed_job(cfg, "wordcount")
    json.dump({"counts": {str(k): v for k, v in r.counts.items()},
               "records": r.records},
              open(os.path.join(outdir, f"counts{pid}.json"), "w"),
              sort_keys=True)
    print("child", pid, "ok")
""")


def _launch_remote(tmp_path, corpus, sub, die):
    outdir = tmp_path / sub
    outdir.mkdir()
    env = td._env(1)  # no jax.distributed: FS-only coordination
    procs = [subprocess.Popen(
        [sys.executable, "-c", _REMOTE_CHILD, str(i), str(corpus),
         str(outdir), str(int(die))],
        env=env, cwd=td.REPO, stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT, text=True) for i in range(2)]
    logs = [p.communicate(timeout=420)[0] for p in procs]
    return outdir, [p.returncode for p in procs], logs


def test_remote_staged_2proc_clean(tmp_path):
    corpus = tmp_path / "c.txt"
    td._write_corpus(corpus, lines=400)
    outdir, codes, logs = _launch_remote(tmp_path, corpus, "clean", False)
    assert codes == [0, 0], "\n".join(logs)
    c0 = json.load(open(outdir / "counts0.json"))
    c1 = json.load(open(outdir / "counts1.json"))
    # the drain is replicated: both processes report the GLOBAL counts
    assert c0["counts"] == c1["counts"]
    # partitioned output covers the key space disjointly
    parts = sorted(p for p in os.listdir(outdir)
                   if p.startswith("out.txt.part"))
    assert len(parts) == 2
    # a stage manifest committed per process, schema-tagged
    m = json.load(open(outdir / "stage" / "manifest.proc0.json"))
    assert m["schema"] == "moxt-shuffle-stage-v1" and m["final"]


def test_remote_staged_sigkill_recovery(tmp_path):
    """Kill process 1 with SIGKILL two chunks into its stage: process 0
    must claim it, re-map only the un-committed chunks, drain every
    partition with the manifest checksums intact, and write output
    byte-identical to an unharmed run."""
    corpus = tmp_path / "c.txt"
    td._write_corpus(corpus, lines=400)
    clean, codes, logs = _launch_remote(tmp_path, corpus, "clean", False)
    assert codes == [0, 0], "\n".join(logs)
    killed, codes, logs = _launch_remote(tmp_path, corpus, "killed", True)
    assert codes[0] == 0, "\n".join(logs)
    assert codes[1] == -9  # genuinely SIGKILLed
    assert (json.load(open(killed / "counts0.json"))["counts"]
            == json.load(open(clean / "counts0.json"))["counts"])
    for part in ("out.txt.part0of2", "out.txt.part1of2"):
        assert ((killed / part).read_bytes()
                == (clean / part).read_bytes())
    # takeover evidence: exactly-one-survivor claim + recovery manifest
    assert (killed / "stage" / "claim.proc1").exists()
    rec = json.load(open(killed / "stage" / "manifest.proc1.rec.json"))
    assert rec["final"] and rec["staged_by"] == 0 and rec["proc"] == 1

"""CPU tests of the benchmark harness (``python -m pytest bench/tests``)."""

import json
import os
import re
import shutil
import subprocess
import sys
import time
from types import SimpleNamespace as NS

import numpy as np
import pytest

from benchlib import harness, trace, traffic
from benchlib.harness import BENCH, REPO, load_json, load_module
from tiny import tiny_cell

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


# --- trace reduction ------------------------------------------------------

def _ev(name, start, dur):
    return NS(name=name, start_ns=float(start), duration_ns=float(dur))


def _planes(dev_ops, dev_modules):
    return [NS(name=f"/device:TPU:{i}", lines=[
        NS(name="XLA Ops", events=[_ev(*e) for e in ops]),
        NS(name="XLA Modules", events=[_ev(*e) for e in mods])])
        for i, (ops, mods) in enumerate(zip(dev_ops, dev_modules))] + [
        NS(name="/host:CPU", lines=[])]


def test_trace_reduction_busy_union_gaps_and_kernels():
    # window 0..1000 ns from the two job annotations; overlapping ops on
    # chip 0 count once; chip 1's op past the window is clipped
    planes = _planes(
        dev_ops=[[("%fusion = f32[2] fusion(x)", 100, 200), ("sort", 250, 150),
                  ("fusion", 700, 100), ("%while = w", 690, 200)],
                 [("%all_to_all.19 = u32[4] all-to-all(y)", 100, 100),
                  ("fusion", 950, 500)]],
        dev_modules=[[("jit_merge(12)", 100, 300), ("jit_merge(13)", 700,
                                                    100)],
                     [("jit_merge(12)", 100, 100)]])
    red = trace.reduce_planes(planes, 2, [(0, 500, "bench/job 1"),
                                          (500, 1000, "bench/job 2")])
    assert red.window_s == pytest.approx(1000e-9)
    assert red.busy_by_device == pytest.approx([500e-9, 150e-9])
    assert red.busy_s == pytest.approx(325e-9)
    assert red.module_s == pytest.approx({"jit_merge": 250e-9})
    assert red.op_s["jit_merge/fusion"] == pytest.approx(300 / 2 * 1e-9)
    assert red.op_s["?/fusion"] == pytest.approx(50 / 2 * 1e-9)
    exchange = load_module(os.path.join(BENCH, "metrics",
                                        "exchange_collective_s.py"), "m_ex")
    assert red.ops_matching(exchange.OPS) == pytest.approx(50e-9)
    # idle on every chip: 0-100, 400-690, 890-950; the while holds the
    # fusion at 700 and is not counted by name
    assert "jit_merge/while" not in red.op_s
    assert sorted(s for _, s in red.gaps) == pytest.approx(
        [60e-9, 100e-9, 290e-9])
    labels = dict((round(s * 1e9), n) for n, s in red.gaps)
    assert labels[100].startswith("bench/job 1")
    assert labels[290].startswith("bench/job 2 +")
    b = red.breakdown()
    assert b["idle_gaps"][0][1] == pytest.approx(290e-9)
    assert len(b["device_ops"]) <= 10


def test_trace_reduction_refuses_missing_chip_or_jobs():
    planes = _planes([[("f", 0, 1)]], [[]])
    with pytest.raises(ValueError):
        trace.reduce_planes(planes, 4, [(0, 10, "bench/job 1")])
    with pytest.raises(ValueError):
        trace.reduce_planes(planes, 1, [])


RECORDED = os.path.join(os.path.dirname(__file__), "data", "v5e.xplane.pb")


def test_trace_reduction_of_a_recorded_chip_trace():
    """A trace recorded on one TPU v5 lite by ``record_trace.py``: three
    jobs, each a 1024^2 matmul and a sort of its 2^20 results."""
    jobs = load_json(RECORDED + ".json")["jobs"]
    red = trace.reduce_trace(RECORDED, 1, jobs)
    assert red.jobs == 3
    assert red.window_s == pytest.approx((jobs[-1][1] - jobs[0][0]) * 1e-9)
    assert 0 < red.busy_s < red.window_s
    assert list(red.module_s) == ["jit__lambda"]
    assert red.modules_matching("^jit_") == pytest.approx(red.busy_s,
                                                          rel=0.05)
    assert any("sort" in k for k in red.op_s)
    # three gaps at least: before, between and after the three runs
    assert len(red.gaps) >= 3
    assert sum(s for _, s in red.gaps) == pytest.approx(
        red.window_s - red.busy_s, rel=1e-6)


# --- cost functions -------------------------------------------------------

def test_kmeans_step_cost_at_known_shapes():
    m = load_module(os.path.join(BENCH, "metrics", "kmeans_step_roofline.py"),
                    "m_kmeans_roofline")
    flops, nbytes = m.cost(n=1000, d=20, k=10, iters=5)
    assert flops == 5 * 4 * 1000 * 20 * 10
    assert nbytes == 5 * 1000 * 20 * 4
    peaks = {"flops": 100e12, "membw": 1e12}
    # memory-bound: 400 kB of reads at 1 TB/s is 0.4 us; a 0.8 us kernel
    # is at half its roofline
    share, bound = m.roofline(flops, nbytes, 0.8e-6, peaks)
    assert (share, bound) == (pytest.approx(50.0), "memory")


# --- the window -----------------------------------------------------------

class _SleepJob:
    input_bytes = 1 << 20
    seed = 2 ** 31 + 9

    def __init__(self, dt):
        self.dt = dt
        self.runs = []

    def setup(self):
        pass

    def run(self, i):
        self.runs.append(i)
        time.sleep(self.dt)
        return {"metrics": {"time/map+reduce_s": self.dt}}

    def check(self, records):
        return [("jobs", len(records), 10 ** 6)]


def test_window_is_whole_jobs_and_rate_counts_all_of_them(tmp_path):
    cell = tiny_cell("wc-enwik9-1chip", tmp_path)
    job = _SleepJob(0.25)
    counter = NS(count=0)
    devs = [NS(platform="cpu", device_kind="cpu",
               memory_stats=lambda: {"peak_bytes_in_use": 7})]
    t0 = time.perf_counter()
    res = harness._run(cell, job, 0.6, False, t0, counter, devs, None,
                       str(tmp_path), lambda m: None)
    # warm-up (-1), then jobs until the first boundary past 0.6 s: three
    assert job.runs == [-1, 0, 1, 2]
    assert res["attempted"] == 3 and res["failed"] == 0 and res["correct"]
    rate = res["metrics"]["input_mib_per_s"]["value"]
    assert 3 / rate == pytest.approx(0.75, rel=0.1)
    assert res["metrics"]["setup_s"]["value"] >= 0.25  # the warm-up job
    assert res["device"]["memory_peak_bytes"] == 7
    assert list(res)[-1] == "checks"


def test_open_loop_offers_seeded_arrivals_and_waits_for_every_job(tmp_path):
    cell = tiny_cell("wc-enwik9-1chip", tmp_path)
    cell.traffic = {"arrivals": "poisson", "rate_per_s": 8.0, "burst": 2,
                    "trace_min_jobs": 1, "trace_min_s": 0.0}
    job = _SleepJob(0.05)
    counter = NS(count=0)
    devs = [NS(platform="cpu", device_kind="cpu", memory_stats=lambda: {})]
    res = harness._run(cell, job, 1.0, False, time.perf_counter(), counter,
                       devs, None, str(tmp_path), lambda m: None)
    want = traffic.poisson_times(8.0, 2, job.seed, 1.0)
    assert res["attempted"] == len(want) == len(job.runs) - 1
    # the window lasts until the last job offered has returned
    rate = res["metrics"]["input_mib_per_s"]["value"]
    assert len(want) / rate >= want[-1] + 0.05


def test_traffic_is_data_read_by_one_generator():
    a = traffic.Offer({"arrivals": "poisson", "rate_per_s": 50.0,
                       "trace_min_jobs": 2, "trace_min_s": 1.0}, 2 ** 33, 4.0)
    b = traffic.Offer({"arrivals": "poisson", "rate_per_s": 50.0,
                       "trace_min_jobs": 2, "trace_min_s": 1.0}, 2 ** 33, 4.0)
    assert np.array_equal(a.times, b.times)
    assert 120 < len(a.times) < 280 and a.times[-1] < 4.0
    assert np.all(np.diff(a.times) >= 0) and a.at(len(a.times), 0.0) is None
    burst = traffic.poisson_times(10.0, 3, 7, 5.0)
    assert np.all(burst[0::3] == burst[2::3])
    closed = traffic.Offer(load_json(os.path.join(
        BENCH, "traffic", "closed_batch.json")), 1, 2.0)
    assert closed.at(5, 1.5) == 1.5 and closed.at(6, 2.0) is None
    for bad in ({"arrivals": "closed", "rate": 1, "trace_min_jobs": 1,
                 "trace_min_s": 1}, {"arrivals": "replay", "trace_min_jobs": 1,
                                     "trace_min_s": 1}):
        with pytest.raises(traffic.TrafficError):
            traffic.Offer(bad, 1, 1.0)
    for f in os.listdir(os.path.join(BENCH, "traffic")):
        traffic.Offer(load_json(os.path.join(BENCH, "traffic", f)), 1, 1.0)


# --- every cell's loop and comparison, tiny, on the CPU --------------------

@pytest.mark.parametrize("name", ["wc-enwik9-1chip", "kmeans-large-1chip",
                                  "wc-enwik9-mesh4"])
def test_cell_runs_and_compares_on_cpu(name, tmp_path):
    cell = tiny_cell(name, tmp_path)
    res = harness.run_cell(cell, 2 ** 31 + 17, 0.5, False,
                           time.perf_counter(), require_tpu=False,
                           backend="cpu", log=lambda m: None,
                           data_root=str(tmp_path))
    assert res["correct"], res["checks"]
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert set(res["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert "setup_s" in res["metrics"]
    assert all(c["value"] <= c["limit"] for c in res["checks"].values())
    assert not os.path.exists(os.path.join(
        str(tmp_path), f"{cell.config_name}-{2 ** 31 + 17}"))


def test_same_seed_same_data_other_seed_other_data(tmp_path):
    wc = load_module(os.path.join(BENCH, "jobs", "wordcount.py"), "wc_t")
    spec = dict(load_json(os.path.join(BENCH, "configs",
                                       "wordcount-enwik9.json"))["data"],
                corpus_bytes=200_000, vocab=500)
    blobs = []
    for i, seed in enumerate([2 ** 31 + 5, 2 ** 31 + 5, 2 ** 31 + 6]):
        c = wc.Corpus(spec, seed)
        wc.write_corpus(c, str(tmp_path / f"{i}.txt"), 3)
        with open(c.path, "rb") as f:
            blobs.append(f.read())
        # the counts are those of the text, split as the job splits it
        got = {}
        for w in blobs[-1].split():
            got[w.lower()] = got.get(w.lower(), 0) + 1
        assert got == c.reference()
        assert len(blobs[-1]) <= spec["corpus_bytes"]
    assert blobs[0] == blobs[1] != blobs[2]


# --- the command ----------------------------------------------------------

def _command(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "wc-enwik9-1chip",
         "--seed", "3", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_command_refuses_to_run_without_a_tpu():
    p = _command(REPO)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert "{" not in p.stdout


def test_command_refuses_without_the_program(tmp_path):
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".data", "__pycache__"))
    p = _command(str(tmp_path), {"PYTHONPATH": ""})
    assert p.returncode != 0
    assert "{" not in p.stdout


# --- BENCHMARK.json against the contract ----------------------------------

def test_benchmark_json_is_whole():
    bm = load_json(os.path.join(REPO, "BENCHMARK.json"))
    assert set(bm) == {"command", "paths", "run_seconds", "configs",
                       "workloads", "end_to_end", "per_layer"}
    assert bm["paths"] == ["bench"]
    assert 1 <= bm["run_seconds"] <= 51
    configs = {c["name"]: c for c in bm["configs"]}
    used = {w["config"] for w in bm["workloads"]}
    assert used == set(configs)
    e2e = {m["name"] for m in bm["end_to_end"]}
    assert "setup_s" in e2e
    for m in bm["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for c in bm["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        conf = load_json(os.path.join(REPO, c["file"]))
        assert conf["name"] == c["name"] and conf["reduced"] == c["reduced"]
        assert all(k in conf for k in c["reduced"])
        assert os.path.exists(os.path.join(BENCH, "jobs",
                                           conf["job"] + ".py"))
    cells = set()
    for w in bm["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        assert os.path.exists(os.path.join(BENCH, "traffic",
                                           w["traffic"] + ".json"))
        assert configs[w["config"]] and w["name"] not in cells
        conf = load_json(os.path.join(REPO, configs[w["config"]]["file"]))
        assert conf["deployment"]["chips"] == w["chips"]
        cells.add(w["name"])
    assert sum(w["chips"] == 4 for w in bm["workloads"]) <= max(
        1, len(cells) // 2)
    for m in bm["per_layer"]:
        assert m["moves"] in e2e
        assert set(m.get("workloads", cells)) <= cells
    for m in bm["per_layer"] + bm["end_to_end"]:
        assert os.path.exists(os.path.join(BENCH, "metrics",
                                           m["name"] + ".py"))
    names = ([c["name"] for c in bm["configs"]] + list(cells)
             + [m["name"] for m in bm["end_to_end"] + bm["per_layer"]])
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    assert len(json.dumps(bm)) < 64 * 1024


def test_readers_return_nothing_without_their_source():
    view = harness.RunView({}, [])
    for f in sorted(os.listdir(os.path.join(BENCH, "metrics"))):
        if f.endswith(".py"):
            m = load_module(os.path.join(BENCH, "metrics", f), "m_" + f[:-3])
            assert m.read(view) is None, f


def test_corpus_counts_and_reference_file_agree_with_wrong_lines():
    wc = load_module(os.path.join(BENCH, "jobs", "wordcount.py"), "wc_t2")
    ref = {b"a": 3, b"b,": 1, b"c.": 2}
    blob = wc.reference_file(ref)
    assert blob == b"a 3\nb, 1\nc. 2\n"
    assert wc.wrong_lines(blob, blob) == 0
    assert wc.wrong_lines(b"a 3\nb, 2\nc. 2\n", blob) == 1
    assert wc.wrong_lines(b"a 3\nc. 2\n", blob) == 1
    assert wc.wrong_lines(b"b, 1\na 3\nc. 2\n", blob) == 1
    assert wc.top_k(ref, 2) == [(b"a", 3), (b"c.", 2)]


def test_kmeans_reference_is_lloyd():
    km = load_module(os.path.join(BENCH, "jobs", "kmeans.py"), "km_t")
    pts = np.array([[0, 0], [10, 0], [0, 1], [10, 1], [5, 9]], np.float32)
    d = os.path.join(BENCH, ".data")
    os.makedirs(d, exist_ok=True)
    path = os.path.join(d, "lloyd_test.npy")
    np.save(path, pts)
    try:
        c = km.lloyd64(path, 2, 3, chunk=2, workers=2)
    finally:
        os.remove(path)
    # start (0,0),(10,0); (5,9) ties and goes to the first centroid
    np.testing.assert_allclose(c, [[5 / 3, 10 / 3], [10, 0.5]])
    assert km.centroid_gap(c * 1.01, c) == pytest.approx(0.01)
    assert km.centroid_gap(np.full_like(c, np.nan), c) == float("inf")

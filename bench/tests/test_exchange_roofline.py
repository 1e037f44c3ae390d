"""The exchange's readers (``exchange_roofline``,
``exchange_collective_s``): the share the formula gives on a trace with
a known all-to-all time, nothing where there is no trace, counter or
all-to-all, and the tiny four-shard cell through the harness on the CPU
with the benchmark's own entries."""

import os
import time

import pytest

from benchlib import harness
from benchlib.harness import BENCH, load_module
from benchlib.trace import Reduction
from tiny import tiny_cell

CELL = "wc-enwik9-mesh4"
SEED = 2 ** 31 + 41


def _reader(name):
    return load_module(os.path.join(BENCH, "metrics", name + ".py"),
                       "m_ex_" + name)


def _trace(all_to_all_s: float) -> Reduction:
    """Two traced jobs: ``all_to_all_s`` of all-to-all on each chip,
    beside a fusion and an all-reduce that the share must not count."""
    return Reduction(window_s=1.0, busy_s=0.5, busy_by_device=[0.5] * 4,
                     op_s={"jit__merge_step/all_to_all.19": all_to_all_s,
                           "jit__merge_step/fusion.3": 0.2,
                           "jit__merge_step/all-reduce.4": 0.001},
                     jobs=2)


def _jobs(payload, method="all_to_all"):
    m = {"shuffle/exchange_collective": method}
    if payload is not None:
        m["shuffle/all_to_all_bytes"] = payload
    return [{"metrics": dict(m)}, {"metrics": dict(m)}]


CONFIG = {"deployment": {"num_shards": 4}}


def test_share_is_bytes_sent_off_chip_over_ici_peak():
    # 23 exchanges a job at the default cap: 16 * 32,784 rows of 12 bytes
    payload = 23 * 16 * 32_784 * 12
    view = harness.RunView(CONFIG, _jobs(payload), trace=_trace(0.004),
                           traced_jobs=2)
    # one chip sends 3/16 of it at 200 GB/s, against 2 ms a job
    want = 100 * (payload * 3 / 16 / 200e9) / 0.002
    assert _reader("exchange_roofline").read(view) == pytest.approx(want)
    assert 0 < want < 100
    # the collectives' reader takes the all-reduce too
    assert _reader("exchange_collective_s").read(view) == pytest.approx(
        (0.004 + 0.001) / 2)


@pytest.mark.parametrize("view", [
    pytest.param(harness.RunView(CONFIG, _jobs(10 ** 8)), id="no-trace"),
    pytest.param(harness.RunView(CONFIG, _jobs(None), trace=_trace(0.004),
                                 traced_jobs=2), id="no-counter"),
    pytest.param(harness.RunView(CONFIG, _jobs(10 ** 8, "all_gather"),
                                 trace=_trace(0.004), traced_jobs=2),
                 id="all-gather"),
    pytest.param(harness.RunView(CONFIG, _jobs(10 ** 8), trace=_trace(0.0),
                                 traced_jobs=2), id="no-all-to-all-op"),
])
def test_share_is_none_without_its_sources(view):
    assert _reader("exchange_roofline").read(view) is None


def test_tiny_mesh_cell_runs_compares_and_reads(tmp_path):
    cell = tiny_cell(CELL, tmp_path)
    assert {"exchange_collective_s", "exchange_roofline"} <= {
        m["name"] for m in cell.per_layer}
    res = harness.run_cell(cell, SEED, 0.5, False, time.perf_counter(),
                           require_tpu=False, backend="cpu",
                           log=lambda m: None, data_root=str(tmp_path))
    assert res["correct"], res["checks"]
    assert res["attempted"] >= 1 and res["failed"] == 0
    # the counters the share reads reach a bench job's metrics, and a
    # later job on the mesh builds no program
    jobmod = load_module(os.path.join(BENCH, "jobs", "wordcount.py"),
                         "wc_ex")
    job = jobmod.Job(cell.config, SEED, str(tmp_path / "data"),
                     backend="cpu")
    job.setup()
    records = [job.run(i) for i in range(2)]
    m = records[-1]["metrics"]
    assert m["shuffle/exchanges"] > 0 and m["shuffle/programs_reused"] > 0
    assert m.get("shuffle/programs_built", 0) == 0
    assert m["compile/total_compiles"] == 0
    got = harness.read_metrics(cell.per_layer, harness.RunView(
        cell.config, records, trace=_trace(0.004), traced_jobs=2))
    payload = sum(r["metrics"]["shuffle/all_to_all_bytes"]
                  for r in records) / 2
    assert got["exchange_roofline"]["value"] == pytest.approx(
        100 * payload * 3 / 16 / 200e9 / 0.002)
    assert got["exchange_collective_s"]["value"] > 0

"""XLA program observatory (ISSUE-5 tentpole): the compile ledger counts
compiles exactly and names recompile causes, the cost-analysis join has
the documented schema, dispatch-gap histograms populate on the CPU mesh,
an injected recompile fails the ledger gate, and the ``obs xprof`` CLI
round-trips a real run's metrics document.
"""

import json

import numpy as np
import pytest

from map_oxidize_tpu.obs.compile import CompileLedger, ObservedJit
from map_oxidize_tpu.obs.xprof import (
    DeviceSampler,
    flatten_report,
    job_report,
    render_report,
)


def _observed(name, fn, ledger, **kw):
    import jax

    return ObservedJit(name, jax.jit(fn), ledger=ledger, **kw)


# --- compile ledger --------------------------------------------------------


def test_compile_counts_on_twice_shaped_program():
    """A program fed two input shapes compiles exactly twice, with the
    second compile named new_input_shape; re-calling either shape adds
    dispatches but no compiles."""
    led = CompileLedger()
    f = _observed("t/add", lambda x: x + 1, led)
    a = np.zeros(8, np.float32)
    b = np.zeros(16, np.float32)
    f(a)
    f(a)
    f(b)
    f(b)
    f(a)
    s = led.programs["t/add"]
    assert s.compiles == 2
    assert s.dispatches == 5
    assert s.causes == ["new_input_shape"]
    assert len(s.sigs) == 2


def test_recompile_cause_new_dtype_and_static():
    led = CompileLedger()
    f = _observed("t/dt", lambda x: x * 2, led)
    f(np.zeros(4, np.float32))
    f(np.zeros(4, np.int32))
    assert led.programs["t/dt"].causes == ["new_dtype"]

    import jax

    g = ObservedJit("t/st", jax.jit(lambda x, k: x[:k], static_argnums=1),
                    ledger=led)
    g(np.zeros(8, np.float32), 2)
    g(np.zeros(8, np.float32), 3)
    assert led.programs["t/st"].causes == ["new_static_config"]


def test_tag_distinguishes_closure_variants():
    """Two jits sharing one program name but differing in closed-over
    statics (the stream step's first/last flags) are told apart by the
    tag, not conflated into a phantom retrace."""
    led = CompileLedger()
    f1 = _observed("t/tag", lambda x: x + 1, led, tag=("first",))
    f2 = _observed("t/tag", lambda x: x + 2, led, tag=("last",))
    x = np.zeros(4, np.float32)
    f1(x)
    f2(x)
    s = led.programs["t/tag"]
    assert s.compiles == 2
    assert s.causes == ["new_static_config"]


def test_cost_analysis_join_schema(monkeypatch):
    """The job report carries FLOPs/bytes from cost_analysis per program,
    achieved rates over the estimated device time, MFU against the env
    peaks, and a memory/compute bound classification."""
    monkeypatch.setenv("MOXT_PEAK_FLOPS", "1e12")
    monkeypatch.setenv("MOXT_PEAK_MEMBW", "1e11")
    led = CompileLedger()
    f = _observed("t/mm", lambda a, b: a @ b, led)
    a = np.ones((64, 64), np.float32)
    for _ in range(3):
        f(a, a)
    report = job_report(led.job_delta({}))
    row = report["programs"]["t/mm"]
    assert row["compiles"] == 1
    assert row["dispatches"] == 3
    assert row["flops_per_dispatch"] and row["flops_per_dispatch"] > 0
    assert row["bytes_per_dispatch"] and row["bytes_per_dispatch"] > 0
    assert row["device_s_est"] and row["device_s_est"] > 0
    assert row["achieved_flops_per_s"] > 0
    assert "mfu_pct" in row and row["mfu_pct"] >= 0
    assert row["bound"] in ("memory", "compute")
    assert report["peaks"]["source"] == "env"
    # the flat projection (what the run ledger gates on)
    flat = flatten_report(report)
    assert flat["compile/t/mm/compiles"] == 1
    assert flat["compile/total_compiles"] == 1
    assert flat["xprof/t/mm/dispatches"] == 3
    # and the rendered table mentions the program
    assert "t/mm" in render_report(report)


def test_job_delta_baseline_windows():
    """Per-job numbers are deltas against the activation snapshot: a
    second job over warm programs sees zero compiles, correct dispatch
    counts, and keeps the cost join."""
    led = CompileLedger()
    f = _observed("t/win", lambda x: x - 1, led)
    x = np.zeros(4, np.float32)
    f(x)                       # job 1: compile + dispatch
    base = {n: p.snapshot() for n, p in led.programs.items()}
    f(x)
    f(x)                       # job 2: two warm dispatches
    d = led.job_delta(base)
    assert d["t/win"]["compiles"] == 0
    assert d["t/win"]["dispatches"] == 2
    assert d["t/win"]["bytes_per_dispatch"] is not None


# --- dispatch-gap histograms on the CPU mesh -------------------------------


@pytest.fixture(scope="module")
def sharded_wordcount(tmp_path_factory):
    from map_oxidize_tpu.config import JobConfig
    from map_oxidize_tpu.parallel import shuffle
    from map_oxidize_tpu.runtime.driver import run_wordcount_job
    from map_oxidize_tpu.workloads.wordcount import make_wordcount

    # mesh programs live for the process: drop those an earlier test built
    # on this mesh, so this job builds and compiles its own and the counts
    # below stay exact
    shuffle._PROGRAMS.clear()
    tmp = tmp_path_factory.mktemp("xprof")
    corpus = tmp / "c.txt"
    # the mapper combines per chunk (6 distinct words -> 6 rows/chunk), so
    # many small chunks against a 64-row feed batch produce several
    # SAME-SHAPE merges: beyond the compiling first dispatch the job has
    # steady-state dispatches for the gap histogram
    corpus.write_bytes(b"alpha beta gamma delta epsilon zeta\n" * 2000)
    metrics_out = tmp / "m.json"
    mapper, reducer = make_wordcount("ascii", use_native=False)
    cfg = JobConfig(input_path=str(corpus), output_path="", backend="cpu",
                    num_shards=8, mapper="python", batch_size=64,
                    chunk_bytes=4096, key_capacity=1 << 12, metrics=False,
                    metrics_out=str(metrics_out))
    result = run_wordcount_job(cfg, mapper, reducer)
    return result, json.loads(metrics_out.read_text())


def test_dispatch_gap_histogram_on_cpu_mesh(sharded_wordcount):
    """A real sharded job populates the dispatch-gap histogram (at least
    one steady-state dispatch beyond the compiling ones) and the shuffle
    merge program appears in the observatory with exact compile counts."""
    result, doc = sharded_wordcount
    m = result.metrics
    assert m.get("device/compute_ms/count", 0) >= 1
    assert m.get("compile/shuffle/merge/compiles") == 1
    assert m.get("compile/total_compiles", 0) >= 2
    progs = doc["xprof"]["programs"]
    assert progs["shuffle/merge"]["dispatches"] >= 1
    assert "device/dispatch_gap_ms" in doc["histograms"]


def test_xprof_cli_roundtrip(sharded_wordcount, capsys):
    """``obs xprof`` renders the report from the metrics document the
    job wrote (and --json re-emits the structured form)."""
    import os

    from map_oxidize_tpu.obs.cli import obs_main

    _result, doc = sharded_wordcount
    # re-materialize the document for the CLI (the fixture parsed it)
    import tempfile

    with tempfile.NamedTemporaryFile("w", suffix=".json",
                                     delete=False) as f:
        json.dump(doc, f)
        path = f.name
    try:
        assert obs_main(["xprof", path]) == 0
        out = capsys.readouterr().out
        assert "XLA program observatory" in out
        assert "shuffle/merge" in out
        assert obs_main(["xprof", path, "--json"]) == 0
        parsed = json.loads(capsys.readouterr().out)
        assert parsed["programs"]["shuffle/merge"]["compiles"] == 1
    finally:
        os.unlink(path)


# --- ledger gate on injected recompiles ------------------------------------


def _entry(ts, compiles, mfu=50.0):
    from map_oxidize_tpu.obs import ledger

    summary = {"time/map+reduce_s": 1.0, "records_in": 100,
               "compile/engine/merge_packed/compiles": compiles,
               "compile/total_compiles": compiles,
               "xprof/engine/merge_packed/mfu_pct": mfu}
    e = {"ts_unix_s": ts, "version": "x", "config_hash": "deadbeef",
         "workload": "wordcount", "corpus_bytes": 1000, "n_processes": 1,
         "phases_s": {"map+reduce": 1.0}, "metrics": summary}
    return e


def test_gate_trips_on_injected_recompile(tmp_path, capsys):
    from map_oxidize_tpu.obs import ledger
    from map_oxidize_tpu.obs.cli import obs_main

    a = _entry(1.0, compiles=1)
    b = _entry(2.0, compiles=2)
    diff = ledger.diff_entries(a, b)
    assert any("recompile regression" in r for r in diff["regressions"])
    # gate_against_previous (the bench.py --gate primitive) flags it too
    ldir = tmp_path / "ledger"
    ledger.append(str(ldir), a)
    ledger.append(str(ldir), b)
    regs = ledger.gate_against_previous(str(ldir), b)
    assert any("recompile" in r for r in regs)
    # and the CLI exits 3 under --gate
    rc = obs_main(["diff", "--ledger-dir", str(ldir), "--gate"])
    capsys.readouterr()
    assert rc == 3
    # identical compile counts do NOT trip (zero-delta self gate)
    assert obs_main(["diff", "--ledger-dir", str(ldir), "--gate",
                     "--", "-1", "-1"]) == 0
    capsys.readouterr()


def test_gate_trips_on_mfu_drop():
    from map_oxidize_tpu.obs import ledger

    a = _entry(1.0, compiles=1, mfu=50.0)
    b = _entry(2.0, compiles=1, mfu=30.0)
    diff = ledger.diff_entries(a, b, threshold_pct=10.0)
    assert any("mfu_pct" in r for r in diff["regressions"])
    # a small wobble under the threshold passes
    c = _entry(3.0, compiles=1, mfu=48.0)
    diff = ledger.diff_entries(a, c, threshold_pct=10.0)
    assert not diff["regressions"]


# --- stall detector --------------------------------------------------------


class _FakeObs:
    def __init__(self):
        from map_oxidize_tpu.obs import MetricsRegistry, Tracer

        self.registry = MetricsRegistry()
        self.tracer = Tracer(enabled=True)
        self.heartbeat = None


def test_stall_detector_fires_once_and_rearms():
    """Chunks at ~1s cadence, then silence: one [stalled] warning naming
    the open spans, no repeat while still stalled, re-armed by the next
    completing chunk."""
    obs = _FakeObs()
    lines = []

    sampler = DeviceSampler(obs, interval_s=0.0, stall_factor=5.0)
    import map_oxidize_tpu.obs.xprof as xprof_mod

    orig_warn = xprof_mod._log.warning
    xprof_mod._log.warning = lambda fmt, *a: lines.append(fmt % a)
    try:
        t = 0.0
        span = obs.tracer.span("phase/map+reduce")
        span.__enter__()
        for i in range(5):
            obs.registry.observe("feed_block_ms", 1.0)
            assert sampler.check_stall(now=t) is False
            t += 1.0
        # silence: below the factor*median threshold -> quiet
        assert sampler.check_stall(now=t + 3.0) is False
        # past it -> exactly one warning, with the open span named
        assert sampler.check_stall(now=t + 6.0) is True
        assert sampler.check_stall(now=t + 7.0) is False
        assert len(lines) == 1
        assert "[stalled]" in lines[0]
        assert "phase/map+reduce" in lines[0]
        assert obs.registry.counters.get("heartbeat/stalls") == 1
        # a completing chunk re-arms the detector
        obs.registry.observe("feed_block_ms", 1.0)
        assert sampler.check_stall(now=t + 8.0) is False
        assert sampler.check_stall(now=t + 20.0) is True
        span.__exit__(None, None, None)
    finally:
        xprof_mod._log.warning = orig_warn


def test_hbm_sampler_noop_on_cpu():
    """CPU devices expose no memory_stats: the sampler must be silent,
    not crash, and record nothing."""
    obs = _FakeObs()
    sampler = DeviceSampler(obs, interval_s=0.1, stall_factor=0.0)
    sampler.sample_once()
    assert not any(k.startswith("hbm/") for k in obs.registry.gauges)

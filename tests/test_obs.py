"""Observability subsystem (moxt.obs): spans, metrics, heartbeat, CLI
round-trip, and the demotion accounting it makes observable.

Covers the ISSUE-1 acceptance surface: span nesting/exception safety,
Chrome trace-event schema validity, histogram quantiles, the
``--metrics-out`` / ``--trace-out`` CLI round trip on a tiny corpus, and
heartbeat emission under a fake clock — all on the CPU test mesh.

ISSUE-3 additions: the run ledger + regression diff (``obs diff``,
``--gate``), trace-shard merging with skew accounting (``obs merge``),
provenance stamping (version + config hash on every export), and the
failure flight recorder — including the regression test that a job
raising mid-phase still flushes partial metrics/trace with its open
spans closed.
"""

import json
import threading

import numpy as np
import pytest

from map_oxidize_tpu.obs import Obs
from map_oxidize_tpu.obs.heartbeat import Heartbeat
from map_oxidize_tpu.obs.metrics import Histogram, MetricsRegistry
from map_oxidize_tpu.obs.trace import NULL_SPAN, Tracer


# --- tracer ---------------------------------------------------------------


def test_span_nesting_records_depth_and_containment():
    t = Tracer(enabled=True)
    with t.span("outer", rows=2):
        with t.span("inner"):
            pass
    events = {e["name"]: e for e in t.chrome_trace() if e["ph"] == "X"}
    outer, inner = events["outer"], events["inner"]
    # child starts after parent and ends before it (time containment is
    # what gives Perfetto the nesting)
    assert inner["ts"] >= outer["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1e-6
    assert outer["args"]["rows"] == 2


def test_span_exception_safety_records_end_and_error():
    t = Tracer(enabled=True)
    with pytest.raises(ValueError):
        with t.span("boom"):
            raise ValueError("broken")
    (ev,) = [e for e in t.chrome_trace() if e["ph"] == "X"]
    assert ev["name"] == "boom"
    assert ev["dur"] >= 0
    assert "ValueError" in ev["args"]["error"]


def test_leaked_child_span_does_not_corrupt_parent_stack():
    t = Tracer(enabled=True)
    outer = t.span("outer")
    inner = t.span("inner")
    outer.__enter__()
    inner.__enter__()
    # outer exits while inner never did (a lower-level crash path):
    # the stack must pop through cleanly and later spans get depth 0
    outer.__exit__(None, None, None)
    with t.span("later"):
        pass
    by_name = {e["name"]: e for e in t._events}
    assert by_name["later"]["depth"] == 0


def test_disabled_tracer_is_noop_and_shared():
    t = Tracer(enabled=False)
    s = t.span("x", rows=1)
    assert s is NULL_SPAN
    with s:
        s.set(more=2)
    t.instant("marker")
    assert t.chrome_trace()[0]["name"] == "process_name"
    assert [e for e in t.chrome_trace() if e["ph"] in ("X", "i")] == []


def test_span_sink_self_time_excludes_same_thread_children_only():
    """Fake clock: a driver span's self time is its duration less its
    same-thread children; a producer-thread span that runs inside it
    takes nothing off it."""
    now = [0.0]
    reg = MetricsRegistry()
    t = Tracer(enabled=False, clock=lambda: now[0], sink=reg.record_span)
    opened, release = threading.Event(), threading.Event()

    def producer():
        with t.span("map/produce"):
            opened.set()
            release.wait(5)

    with t.span("phase/map+reduce"):          # 0 .. 10
        now[0] = 1.0
        th = threading.Thread(target=producer)
        th.start()                            # produce 1 .. 8
        opened.wait(5)
        now[0] = 2.0
        with t.span("engine/flush"):          # 2 .. 5
            now[0] = 3.0
            with t.span("engine/put"):        # 3 .. 4
                now[0] = 4.0
            now[0] = 5.0
        now[0] = 8.0
        release.set()
        th.join()
        now[0] = 10.0
    c = reg.counters
    assert c["span/phase/map+reduce_s"] == 10.0
    assert c["span/phase/map+reduce_self_s"] == 7.0
    assert c["span/engine/flush_s"] == 3.0
    assert c["span/engine/flush_self_s"] == 2.0
    assert c["span/engine/put_self_s"] == 1.0
    assert c["span/map/produce_s"] == c["span/map/produce_self_s"] == 7.0
    assert c["span/engine/flush_n"] == 1
    # the keys older readers know, from the same spans
    assert reg.phases["map+reduce"] == 10.0
    assert c["pipeline/produce_ms"] == 7000.0
    assert reg.histograms["engine/flush_ms"].total == 3000.0


def test_span_sink_totals_without_trace_out():
    """Tracing off: an Obs bundle's spans still land in its registry, and
    no event list is kept."""
    from map_oxidize_tpu.config import JobConfig

    obs = Obs.from_config(JobConfig(input_path="unused"))
    assert not obs.tracer.enabled
    with obs.phase("map+reduce"):
        with obs.feed_span(rows=3):
            pass
    obs.tracer.instant("marker")
    s = obs.registry.summary()
    assert s["span/engine/feed_block_n"] == 1
    assert s["span/phase/map+reduce_s"] >= s["span/engine/feed_block_s"]
    assert s["span/job/setup_n"] == 1     # closed by the first phase
    assert "time/map+reduce_s" in s and s["feed_block_ms/count"] == 1
    assert obs.tracer._events == []
    assert [e for e in obs.tracer.chrome_trace() if e["ph"] != "M"] == []


def test_feed_wait_counter_is_the_sum_of_feed_wait_spans():
    from map_oxidize_tpu.config import JobConfig
    from map_oxidize_tpu.runtime.pipeline import ChunkPrefetcher

    obs = Obs.from_config(JobConfig(input_path="unused", trace_out="-"))
    assert list(ChunkPrefetcher(iter(range(50)), 2, name="map",
                                obs=obs)) == list(range(50))
    waits = [e["dur"] for e in obs.tracer._events
             if e["name"] == "map/feed_wait"]
    assert len(waits) == 51               # one per item, one for the end
    c = obs.registry.counters
    assert c["pipeline/feed_wait_ms"] == pytest.approx(sum(waits) / 1e3,
                                                       rel=1e-9)
    assert c["pipeline/chunks"] == 50


def test_feed_and_flush_histograms_keep_their_counts(tiny_corpus):
    """One ``feed_block_ms`` observation per fed chunk and one
    ``engine/flush_ms`` per device shipment, as the hand timers gave."""
    from map_oxidize_tpu.config import JobConfig
    from map_oxidize_tpu.runtime import run_job

    r = run_job(JobConfig(input_path=str(tiny_corpus), output_path="",
                          num_shards=1, metrics=False, chunk_bytes=256,
                          batch_size=512), "wordcount")
    m = r.metrics
    assert m["chunks"] > 1
    assert m["feed_block_ms/count"] == m["chunks"]
    assert m["engine/flush_ms/count"] == m["engine/flushes"] >= 1
    assert m["span/engine/flush_n"] == m["engine/flushes"]


def test_chrome_trace_schema_and_json_round_trip():
    t = Tracer(enabled=True)
    with t.span("a", bytes=np.int64(7), dev=np.int32(0)):
        t.instant("mark", gen=1)
    blob = json.dumps(t.chrome_trace())  # numpy attrs must serialize
    events = json.loads(blob)
    assert isinstance(events, list) and events
    for e in events:
        assert e["ph"] in ("X", "i", "M")
        assert isinstance(e["name"], str)
        if e["ph"] != "M":
            assert isinstance(e["ts"], (int, float)) and e["ts"] >= 0
            assert isinstance(e["pid"], int) and isinstance(e["tid"], int)
        if e["ph"] == "X":
            assert e["dur"] >= 0
    assert any(e["ph"] == "X" and e["args"]["bytes"] == 7 for e in events)


def test_tracer_thread_safety_spans_from_workers():
    t = Tracer(enabled=True)

    def work(i):
        with t.span(f"w{i}"):
            with t.span(f"w{i}/child"):
                pass

    threads = [threading.Thread(target=work, args=(i,)) for i in range(8)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    xs = [e for e in t.chrome_trace() if e["ph"] == "X"]
    assert len(xs) == 16
    # each worker thread got its own tid; children share their parent's
    tids = {e["name"]: e["tid"] for e in xs}
    for i in range(8):
        assert tids[f"w{i}"] == tids[f"w{i}/child"]


def test_jsonl_export(tmp_path):
    t = Tracer(enabled=True)
    with t.span("outer"):
        with t.span("inner"):
            pass
    p = tmp_path / "events.jsonl"
    t.write_jsonl(str(p))
    rows = [json.loads(line) for line in p.read_text().splitlines()]
    depths = {r["name"]: r["depth"] for r in rows}
    assert depths == {"outer": 0, "inner": 1}


# --- histograms / registry ------------------------------------------------


def test_histogram_quantiles_exact_path():
    h = Histogram()
    for v in range(1, 101):
        h.observe(v)
    s = h.summary()
    assert s["count"] == 100
    assert s["max"] == 100
    assert abs(s["mean"] - 50.5) < 1e-9
    assert 45 <= s["p50"] <= 56
    assert 90 <= s["p95"] <= 100


def test_histogram_decimation_bounds_memory_keeps_quantiles():
    h = Histogram(max_samples=256)
    n = 100_000
    for v in range(n):
        h.observe(v)
    assert len(h._samples) < 256
    assert h.count == n
    assert h.max == n - 1 and h.min == 0
    # stride-sampled quantiles stay in the right decile
    assert 0.35 * n <= h.quantile(0.5) <= 0.65 * n
    assert h.quantile(0.95) >= 0.85 * n


def test_registry_summary_is_seed_compatible():
    r = MetricsRegistry()
    with r.phase("map+reduce"):
        pass
    r.count("chunks", 3)
    r.set("records_in", 1000)
    r.observe("feed_block_ms", 2.0)
    r.observe("feed_block_ms", 4.0)
    s = r.summary()
    assert "time/map+reduce_s" in s
    assert s["chunks"] == 3
    assert s["records_in"] == 1000
    assert "records_per_sec" in s  # derived, as the seed Metrics did
    assert s["feed_block_ms/count"] == 2
    assert s["feed_block_ms/max"] == 4.0
    d = r.to_dict()
    assert set(d) == {"phases_s", "counters", "gauges", "histograms"}
    json.dumps(d)  # the --metrics-out document must be valid JSON


def test_registry_gauge_max_watermark():
    r = MetricsRegistry()
    r.gauge_max("peak", 10)
    r.gauge_max("peak", 5)
    r.gauge_max("peak", 20)
    assert r.gauges["peak"] == 20


def test_profiling_shim_still_importable():
    # the seed import path must keep working (drivers outside the repo)
    from map_oxidize_tpu.utils.profiling import Metrics

    m = Metrics()
    with m.phase("x"):
        pass
    assert "time/x_s" in m.summary()


# --- heartbeat (fake clock) ----------------------------------------------


def test_heartbeat_emits_on_interval_with_fake_clock():
    now = [0.0]
    lines = []
    hb = Heartbeat(total_bytes=1000, interval_s=10.0,
                   clock=lambda: now[0], emit=lines.append)
    hb.set_phase("map+reduce")
    hb.update(rows=100, bytes_done=100)   # t=0: within interval, no beat
    assert lines == []
    now[0] = 5.0
    hb.update(rows=100, bytes_done=200)   # t=5: still within
    assert lines == []
    now[0] = 10.0
    hb.update(rows=100, bytes_done=300)   # t=10: beat
    assert len(lines) == 1
    assert "phase=map+reduce" in lines[0]
    assert "rows=300" in lines[0]
    assert "30.0%" in lines[0]
    assert "eta=" in lines[0]
    now[0] = 15.0
    hb.update(rows=100, bytes_done=400)   # within the next interval
    assert len(lines) == 1
    now[0] = 20.0
    hb.update(rows=100, bytes_done=1000)  # next beat, now 100%: no eta
    assert len(lines) == 2
    assert "100.0%" in lines[1]
    assert "eta=" not in lines[1]
    assert hb.beats == 2


def test_heartbeat_fraction_override_and_final_beat():
    now = [0.0]
    lines = []
    hb = Heartbeat(total_bytes=None, interval_s=60.0,
                   clock=lambda: now[0], emit=lines.append)
    hb.set_phase("iterate")
    now[0] = 1.0
    hb.update(rows=50, fraction=0.5)
    assert lines == []          # interval not elapsed
    hb.final_beat()             # jobs shorter than one interval still report
    assert len(lines) == 1
    assert "50.0%" in lines[0]


def test_heartbeat_rejects_bad_interval():
    with pytest.raises(ValueError):
        Heartbeat(interval_s=0)


# --- CLI round trip (tiny corpus, CPU) ------------------------------------


@pytest.fixture
def tiny_corpus(tmp_path):
    p = tmp_path / "tiny.txt"
    p.write_bytes(b"the quick brown fox jumps over the lazy dog\n" * 50)
    return p


def test_cli_metrics_and_trace_round_trip(tmp_path, tiny_corpus, capsys):
    from map_oxidize_tpu.cli import main

    m = tmp_path / "m.json"
    t = tmp_path / "t.json"
    rc = main(["wordcount", str(tiny_corpus),
               "--output", str(tmp_path / "out.txt"),
               "--metrics-out", str(m), "--trace-out", str(t),
               "--progress", "--progress-interval", "0.001",
               "--num-shards", "1", "--quiet"])
    assert rc == 0
    assert "Top 10 words:" in capsys.readouterr().out

    md = json.loads(m.read_text())
    # phase timings, counters, and at least one histogram (acceptance)
    assert "map+reduce" in md["phases_s"]
    assert md["phases_s"]["map+reduce"] > 0
    assert md["counters"]  # engine flush/put counters at minimum
    assert "feed_block_ms" in md["histograms"]
    assert md["histograms"]["feed_block_ms"]["count"] >= 1
    assert md["gauges"]["records_in"] == 450
    assert md["gauges"]["mem/host_rss_peak_bytes"] > 0

    td = json.loads(t.read_text())
    names = [e["name"] for e in td if e["ph"] == "X"]
    # spans cover map, reduce (the fused streaming phase), and finalize
    assert "phase/map+reduce" in names
    assert "phase/finalize" in names
    assert "engine/feed_block" in names
    # nesting: the feed span sits inside the map+reduce phase span
    by = {e["name"]: e for e in td if e["ph"] == "X"}
    ph, feed = by["phase/map+reduce"], by["engine/feed_block"]
    assert ph["ts"] <= feed["ts"]
    assert feed["ts"] + feed["dur"] <= ph["ts"] + ph["dur"] + 1e-6


def test_cli_invertedindex_trace_covers_collect(tmp_path, tiny_corpus):
    from map_oxidize_tpu.cli import main

    t = tmp_path / "t.json"
    rc = main(["invertedindex", str(tiny_corpus),
               "--output", str(tmp_path / "out.txt"),
               "--trace-out", str(t), "--num-shards", "1", "--quiet"])
    assert rc == 0
    names = [e["name"] for e in json.loads(t.read_text())
             if e["ph"] == "X"]
    assert "phase/map+collect" in names
    assert "phase/sort+postings" in names


def test_result_trace_without_file(tiny_corpus):
    from map_oxidize_tpu.config import JobConfig
    from map_oxidize_tpu.runtime import run_job

    cfg = JobConfig(input_path=str(tiny_corpus), output_path="",
                    num_shards=1, metrics=False, trace_out="-")
    r = run_job(cfg, "wordcount")
    assert isinstance(r.trace, list)
    assert any(e.get("name") == "phase/finalize" for e in r.trace)
    # tracing off -> None, and metrics stay populated
    r2 = run_job(JobConfig(input_path=str(tiny_corpus), output_path="",
                           num_shards=1, metrics=False), "wordcount")
    assert r2.trace is None
    assert r2.metrics["records_in"] == 450


# --- sharded demotion accounting (ADVICE r5 regression) -------------------


def test_sharded_collect_demotion_rows_fed_parity(rng):
    """The demotion-triggering feed must not double-count its own block:
    after the handoff the host engine's rows_fed equals the sharded
    engine's, and the spill counters the new registry records stay
    consistent with the rows actually fed."""
    from map_oxidize_tpu.api import MapOutput
    from map_oxidize_tpu.config import JobConfig
    from map_oxidize_tpu.parallel.collect import ShardedCollectEngine

    cfg = JobConfig(input_path="unused", backend="cpu", num_shards=8,
                    batch_size=512)
    eng = ShardedCollectEngine(cfg, max_rows=600)
    obs = Obs.from_config(cfg)
    eng.obs = obs

    def block(n):
        hi = rng.integers(0, 1 << 31, n).astype(np.uint32)
        lo = rng.integers(0, 1 << 31, n).astype(np.uint32)
        vals = np.zeros((n, 2), np.uint32)
        vals[:, 1] = np.arange(n, dtype=np.uint32)
        return MapOutput(hi=hi, lo=lo, values=vals, records_in=n)

    eng.feed(block(500))          # under max_rows: stays on device
    assert eng._host is None
    eng.feed(block(200))          # crosses 600: demotes, then feeds
    assert eng._host is not None
    assert eng.rows_fed == 700
    assert eng._host.rows_fed == 700   # parity — was 900 pre-fix
    eng.feed(block(100))          # already-demoted branch keeps parity
    assert eng.rows_fed == 800
    assert eng._host.rows_fed == 800
    assert obs.registry.counters["demote/events"] == 1
    # past max_rows the demoted host engine spills to disk buckets; every
    # fed pair must come back through the spilled CSR — an off-by-a-block
    # rows_fed skew would have started the spill one block early and the
    # spill/rows counter makes the volume observable
    terms, offsets, docs, holder = eng.finalize_spilled_csr()
    assert int(offsets[-1]) == 800
    assert obs.registry.counters["spill/rows"] == 800


# --- provenance stamping (ISSUE-3 satellite) -------------------------------


def test_exports_carry_version_and_config_hash(tmp_path, tiny_corpus):
    from map_oxidize_tpu import __version__
    from map_oxidize_tpu.cli import build_parser, config_from_args, main
    from map_oxidize_tpu.obs.ledger import config_hash

    m = tmp_path / "m.json"
    t = tmp_path / "t.json"
    rc = main(["wordcount", str(tiny_corpus), "--output", "",
               "--metrics-out", str(m), "--trace-out", str(t),
               "--num-shards", "1", "--quiet"])
    assert rc == 0
    md = json.loads(m.read_text())
    # the hash is a function of the ENGINE-relevant fields only: the same
    # run minus its artifact flags hashes identically
    want_hash = config_hash(config_from_args(build_parser().parse_args(
        ["wordcount", str(tiny_corpus), "--output", "other.txt",
         "--num-shards", "1"])))
    assert md["meta"]["version"] == __version__
    assert md["meta"]["config_hash"] == want_hash
    assert md["meta"]["workload"] == "wordcount"
    td = json.loads(t.read_text())
    meta = [e for e in td if e.get("name") == "moxt_meta"]
    assert len(meta) == 1
    assert meta[0]["args"]["config_hash"] == want_hash


def test_config_hash_ignores_artifact_paths_only():
    from map_oxidize_tpu.config import JobConfig
    from map_oxidize_tpu.obs.ledger import config_hash

    base = JobConfig()
    assert config_hash(base) == config_hash(
        JobConfig(output_path="elsewhere.txt", metrics_out="m.json",
                  trace_out="t.json", ledger_dir="L", crash_dir="C",
                  progress=True))
    # engine-relevant fields DO change the hash
    assert config_hash(base) != config_hash(JobConfig(num_shards=4))
    assert config_hash(base) != config_hash(JobConfig(tokenizer="unicode"))
    # per-process addressing must NOT: the CLI sets a different
    # dist_process_id on every participant of ONE job, and shard merging
    # refuses mixed config hashes — slot/coordinator are not identity
    assert config_hash(
        JobConfig(dist_coordinator="a:1", dist_num_processes=2,
                  dist_process_id=0)) == config_hash(
        JobConfig(dist_coordinator="b:2", dist_num_processes=2,
                  dist_process_id=1))
    # ...but the process COUNT is (it changes the collective topology)
    assert config_hash(
        JobConfig(dist_coordinator="a:1", dist_num_processes=2,
                  dist_process_id=0)) != config_hash(
        JobConfig(dist_coordinator="a:1", dist_num_processes=4,
                  dist_process_id=0))


# --- run ledger + regression diff ------------------------------------------


def _entry(ledger, workload="wordcount", rate=1000.0, phases=None, ts=1.0):
    from map_oxidize_tpu import __version__

    return {"ts_unix_s": ts, "version": __version__,
            "config_hash": "cafe0123cafe0123", "workload": workload,
            "corpus_bytes": 1 << 20, "n_processes": 1,
            "phases_s": dict(phases or {"map+reduce": 1.0}),
            "metrics": {"records_per_sec": rate, "records_in": 1000}}


def test_ledger_append_read_and_zero_delta_diff(tmp_path):
    from map_oxidize_tpu.obs import ledger

    d = str(tmp_path / "led")
    e = _entry(ledger)
    ledger.append(d, e)
    ledger.append(d, dict(e, ts_unix_s=2.0))
    got = ledger.read(d)
    assert len(got) == 2
    diff = ledger.diff_entries(got[0], got[1])
    assert diff["regressions"] == []
    assert diff["warnings"] == []
    # a self-diff prints and flags nothing (the check.sh smoke contract)
    self_diff = ledger.diff_entries(got[1], got[1])
    assert self_diff["regressions"] == []


def test_ledger_diff_flags_slow_phase_and_throughput_drop(tmp_path):
    from map_oxidize_tpu.obs import ledger

    a = _entry(ledger, phases={"map+reduce": 1.0}, rate=1000.0)
    b = _entry(ledger, phases={"map+reduce": 1.5}, rate=700.0, ts=2.0)
    diff = ledger.diff_entries(a, b, threshold_pct=10.0)
    joined = "\n".join(diff["regressions"])
    assert "map+reduce" in joined
    assert "records_per_sec" in joined
    # below threshold: quiet
    c = _entry(ledger, phases={"map+reduce": 1.05}, rate=980.0, ts=3.0)
    assert ledger.diff_entries(a, c, threshold_pct=10.0)["regressions"] == []


def test_ledger_diff_refuses_apples_to_oranges(tmp_path):
    from map_oxidize_tpu.obs import ledger

    a = _entry(ledger)
    b = dict(_entry(ledger, ts=2.0), config_hash="beef4567beef4567")
    with pytest.raises(ledger.LedgerMismatch):
        ledger.diff_entries(a, b)
    # force downgrades the refusal to a warning
    diff = ledger.diff_entries(a, b, force=True)
    assert any("config_hash" in w for w in diff["warnings"])
    with pytest.raises(ledger.LedgerMismatch):
        ledger.diff_entries(a, dict(_entry(ledger, ts=2.0),
                                    workload="bigram"))
    # corpus size is identity too: the config hash excludes input paths,
    # so a 64MB run must not diff/gate against a 10GB run
    with pytest.raises(ledger.LedgerMismatch):
        ledger.diff_entries(a, dict(_entry(ledger, ts=2.0),
                                    corpus_bytes=10 << 30))


def test_ledger_gate_skips_different_corpus_size(tmp_path):
    from map_oxidize_tpu.obs import ledger

    d = str(tmp_path / "led")
    ledger.append(d, _entry(ledger, rate=1000.0, ts=1.0))
    other = dict(_entry(ledger, rate=100.0, ts=2.0), corpus_bytes=10 << 30)
    assert ledger.gate_against_previous(d, other, 10.0) == []


def test_ledger_gate_against_previous(tmp_path):
    from map_oxidize_tpu.obs import ledger

    d = str(tmp_path / "led")
    ledger.append(d, _entry(ledger, rate=1000.0, ts=1.0))
    ok = _entry(ledger, rate=990.0, ts=2.0)
    assert ledger.gate_against_previous(d, ok, 10.0) == []
    bad = _entry(ledger, rate=500.0, ts=3.0)
    regs = ledger.gate_against_previous(d, bad, 10.0)
    assert regs and "records_per_sec" in regs[0]
    # no prior comparable entry -> nothing to gate
    other = _entry(ledger, workload="bigram", ts=4.0)
    assert ledger.gate_against_previous(d, other, 10.0) == []


def test_cli_ledger_roundtrip_and_diff(tmp_path, tiny_corpus, capsys):
    """End-to-end: two CLI runs append ledger entries; `obs diff` on them
    prints per-phase deltas, and a gated self-diff is all-zero.  The
    prev-vs-last diff deliberately runs WITHOUT --gate: two sub-second
    runs on a loaded test host jitter past any sane threshold, and the
    gate's regression behavior is pinned by the injected-slowdown test
    below, not by wall-clock luck here."""
    from map_oxidize_tpu.cli import main

    led = str(tmp_path / "led")
    for _ in range(2):
        rc = main(["wordcount", str(tiny_corpus), "--output", "",
                   "--ledger-dir", led, "--num-shards", "1", "--quiet"])
        assert rc == 0
    rc = main(["obs", "diff", "--ledger-dir", led])
    out = capsys.readouterr().out
    assert rc == 0
    assert "ledger diff: wordcount" in out
    assert "phase/map+reduce_s" in out
    rc = main(["obs", "diff", "--ledger-dir", led, "--gate", "--",
               "-1", "-1"])
    assert rc == 0
    assert "no regressions" in capsys.readouterr().out


def test_cli_diff_gate_exits_nonzero_on_injected_slowdown(tmp_path,
                                                          capsys):
    from map_oxidize_tpu.cli import main
    from map_oxidize_tpu.obs import ledger

    led = str(tmp_path / "led")
    ledger.append(led, _entry(ledger, phases={"map+reduce": 1.0},
                              rate=1000.0, ts=1.0))
    ledger.append(led, _entry(ledger, phases={"map+reduce": 2.0},
                              rate=400.0, ts=2.0))
    rc = main(["obs", "diff", "--ledger-dir", led, "--gate"])
    out = capsys.readouterr().out
    assert rc == 3
    assert "regressions beyond threshold" in out
    assert "phase map+reduce" in out


# --- shard merge + skew ----------------------------------------------------


def _fake_shard(process, wall_start, records, work_ms):
    """A minimal but schema-true shard: one map span + one flag span."""
    t = Tracer(enabled=True)
    t.wall_start = wall_start
    with t.span("dist/map_chunk", index=0):
        pass
    with t.span("dist/lockstep_flag"):
        pass
    events = t.chrome_trace()
    # give the map span a known duration (fake work)
    for e in events:
        if e.get("name") == "dist/map_chunk":
            e["dur"] = work_ms * 1000.0
    r = MetricsRegistry()
    r.set("records_in", records)
    r.set("device_rows_fed", records // 2)
    r.count("shuffle/all_to_all_bytes", 1024)
    meta = {"version": "x", "config_hash": "h", "workload": "wordcount",
            "process": process, "n_processes": 2,
            "wall_start_unix_s": wall_start}
    return {"schema": "moxt-obs-shard-v1", "meta": meta,
            "events": events, "metrics": dict(r.to_dict(), meta=meta)}


def test_merge_shards_pids_time_alignment_and_skew():
    from map_oxidize_tpu.obs.merge import merge_shards

    s0 = _fake_shard(0, wall_start=100.0, records=600, work_ms=50.0)
    s1 = _fake_shard(1, wall_start=100.5, records=400, work_ms=10.0)
    events, skew = merge_shards([s0, s1])
    xs = [e for e in events if e["ph"] == "X"]
    assert {e["pid"] for e in xs} == {0, 1}
    # proc 1 started 0.5s later: its events shift +5e5 us onto the shared
    # axis
    p1_ts = min(e["ts"] for e in xs if e["pid"] == 1)
    assert p1_ts >= 5e5
    # per-process process_name metadata rows, slot-keyed
    names = {e["pid"]: e["args"]["name"] for e in events
             if e.get("name") == "process_name"}
    assert names == {0: "proc 0", 1: "proc 1"}
    assert skew["records_total"] == 1000
    assert skew["rows_fed_total"] == 500
    assert [r["process"] for r in skew["straggler_ranking"]] == [0, 1]
    assert skew["skew"]["records_in"]["max_over_mean"] == pytest.approx(1.2)


def test_merge_refuses_mixed_identity_shards():
    from map_oxidize_tpu.obs.merge import merge_shards

    s0 = _fake_shard(0, 100.0, 1, 1.0)
    s1 = _fake_shard(1, 100.0, 1, 1.0)
    s1["meta"] = dict(s1["meta"], config_hash="other")
    with pytest.raises(ValueError):
        merge_shards([s0, s1])
    dup = _fake_shard(0, 100.0, 1, 1.0)
    with pytest.raises(ValueError):
        merge_shards([s0, dup])


def test_obs_merge_cli(tmp_path, capsys):
    from map_oxidize_tpu.cli import main
    from map_oxidize_tpu.obs import write_json_atomic

    base = str(tmp_path / "trace.json")
    for p, rec in ((0, 30), (1, 70)):
        write_json_atomic(f"{base}.proc{p}",
                          _fake_shard(p, 100.0 + p, rec, 1.0), indent=None)
    rc = main(["obs", "merge", base])
    out = capsys.readouterr().out
    assert rc == 0
    assert "merged 2 shards" in out
    merged = json.loads((tmp_path / "trace.json").read_text())
    assert {e["pid"] for e in merged if e["ph"] == "X"} == {0, 1}
    skew = json.loads((tmp_path / "trace.json.skew.json").read_text())
    assert skew["records_total"] == 100
    # missing shards -> clean error exit
    assert main(["obs", "merge", str(tmp_path / "nope.json")]) == 2


# --- failure flight recorder (ISSUE-3 satellite regression test) -----------


class _BoomMapper:
    """Raises after one good chunk — mid-map+reduce, spans open."""

    value_shape = ()
    value_dtype = np.int32
    keys_have_dictionary = True
    wide_keys = False
    conserves_counts = True

    def __init__(self):
        self.calls = 0

    def map_chunk(self, chunk):
        from map_oxidize_tpu.workloads.wordcount import WordCountMapper

        self.calls += 1
        if self.calls > 1:
            raise RuntimeError("boom mid-phase")
        return WordCountMapper("ascii", use_native=False).map_chunk(chunk)


def test_job_raise_mid_phase_still_flushes_partial_obs(tmp_path):
    """The ISSUE-3 regression: Obs.finish used to be skipped entirely
    when the job raised, losing trace and metrics.  Now the flight
    recorder closes open spans and flushes partial artifacts to the
    configured paths, plus a crash bundle when --crash-dir is set."""
    from map_oxidize_tpu.api import SumReducer
    from map_oxidize_tpu.config import JobConfig
    from map_oxidize_tpu.runtime.driver import run_wordcount_job

    corpus = tmp_path / "c.txt"
    corpus.write_bytes(b"aa bb cc\n" * 400)
    t = tmp_path / "t.json"
    m = tmp_path / "m.json"
    crash = tmp_path / "crash"
    cfg = JobConfig(input_path=str(corpus), output_path="", num_shards=1,
                    metrics=False, chunk_bytes=1024, num_map_workers=1,
                    max_retries=0, mapper="python", use_native=False,
                    trace_out=str(t), metrics_out=str(m),
                    crash_dir=str(crash))
    with pytest.raises(RuntimeError, match="boom mid-phase"):
        run_wordcount_job(cfg, _BoomMapper(), SumReducer())

    # partial artifacts flushed to the configured paths; the interrupted
    # phase span is closed (its own __exit__ ran during unwinding) and
    # carries the error — genuinely leaked spans get `unfinished=True`
    # via close_open_spans (covered below, across threads)
    td = json.loads(t.read_text())
    phases = [e for e in td if e["ph"] == "X"
              and e["name"] == "phase/map+reduce"]
    assert len(phases) == 1
    assert "boom mid-phase" in phases[0]["args"]["error"]
    md = json.loads(m.read_text())
    assert md["gauges"]["aborted"] is True
    assert md["meta"]["workload"] == "wordcount"

    # crash bundle: config + metrics + well-formed trace + traceback
    bundles = list(crash.iterdir())
    assert len(bundles) == 1
    err = json.loads((bundles[0] / "error.json").read_text())
    assert "boom mid-phase" in err["error"]
    assert err["config"]["input_path"] == str(corpus)
    assert "Traceback" in err["traceback"]
    bm = json.loads((bundles[0] / "metrics.json").read_text())
    assert "map+reduce" in bm["phases_s"]
    bt = json.loads((bundles[0] / "trace.json").read_text())
    for e in bt:  # well-formed trace-event JSON
        assert e["ph"] in ("X", "i", "M")
        if e["ph"] == "X":
            assert e["dur"] >= 0


def test_conservation_failure_leaves_flight_bundle(tmp_path, monkeypatch):
    """The acceptance-named abort path: an injected conservation-check
    failure (driver invariant, not a mapper error) leaves a bundle."""
    from map_oxidize_tpu.config import JobConfig
    from map_oxidize_tpu.runtime import driver, run_job

    corpus = tmp_path / "c.txt"
    corpus.write_bytes(b"aa bb cc dd\n" * 100)
    orig = driver.LazyCounts.total
    monkeypatch.setattr(driver.LazyCounts, "total",
                        lambda self: orig(self) + 7)
    crash = tmp_path / "crash"
    cfg = JobConfig(input_path=str(corpus), output_path="", num_shards=1,
                    metrics=False, crash_dir=str(crash))
    with pytest.raises(RuntimeError, match="conservation violated"):
        run_job(cfg, "wordcount")
    (bundle,) = list(crash.iterdir())
    err = json.loads((bundle / "error.json").read_text())
    assert "conservation violated" in err["error"]
    bm = json.loads((bundle / "metrics.json").read_text())
    # evidence of the run so far: phase clocks + engine counters survive
    assert bm["phases_s"]["map+reduce"] > 0
    # no trace.json: the run did not ask for tracing
    assert not (bundle / "trace.json").exists()


def test_record_failure_never_masks_original_error(tmp_path):
    """A broken crash_dir (a FILE in the way) must not raise out of the
    recorder — the job's own exception is the one the caller sees."""
    from map_oxidize_tpu.config import JobConfig
    from map_oxidize_tpu.obs import flight

    blocker = tmp_path / "crash"
    blocker.write_text("not a directory")
    cfg = JobConfig(input_path="missing", crash_dir=str(blocker))
    obs = Obs.from_config(cfg)
    assert flight.record_failure(obs, cfg, RuntimeError("orig")) is None


def test_close_open_spans_across_threads():
    t = Tracer(enabled=True)
    started = threading.Event()
    release = threading.Event()
    worker_span = []

    def work():
        s = t.span("worker/outer")
        s.__enter__()
        worker_span.append(s)
        started.set()
        release.wait(5)
        s.__exit__(None, None, None)  # unwinds AFTER the force-close

    th = threading.Thread(target=work)
    th.start()
    started.wait(5)
    t.span("driver/phase").__enter__()
    closed = t.close_open_spans(error="sim")
    release.set()
    th.join()
    assert closed == 2
    xs = [e for e in t.chrome_trace() if e["ph"] == "X"]
    # the worker's late __exit__ must NOT record a duplicate
    assert len(xs) == 2
    by = {e["name"]: e for e in xs}
    assert by["worker/outer"]["args"]["unfinished"] is True
    assert by["driver/phase"]["args"]["error"] == "sim"
    # each leaked span is attributed to its OWNING thread's track
    assert by["worker/outer"]["tid"] != by["driver/phase"]["tid"]

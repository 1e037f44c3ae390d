"""Golden-parity tests: full pipeline vs the pure-Python reference model
(SURVEY.md §4 test strategy — the reference itself ships no tests)."""

import numpy as np
import pytest

from map_oxidize_tpu.config import JobConfig
from map_oxidize_tpu.io.splitter import iter_chunks, split_round_robin
from map_oxidize_tpu.runtime.driver import run_wordcount_job
from map_oxidize_tpu.workloads.reference_model import top_k_model, wordcount_model
from map_oxidize_tpu.workloads.wordcount import make_wordcount

CORPUS = b"""To be, or not to be, that is the question:
Whether 'tis nobler in the mind to suffer
The slings and arrows of outrageous fortune,
Or to take arms against a sea of troubles
And by opposing end them. To die-to sleep,
No more; and by a sleep to say we end
The heart-ache and the thousand natural shocks
That flesh is heir to: 'tis a consummation
Devoutly to be wish'd. To die, to sleep;
To sleep, perchance to dream-ay, there's the rub:
"""


@pytest.fixture
def corpus_file(tmp_path):
    # repeat so chunking actually kicks in
    p = tmp_path / "shakes.txt"
    p.write_bytes(CORPUS * 50)
    return str(p)


def _run(corpus_file, tmp_path, **overrides):
    cfg = JobConfig(
        input_path=corpus_file,
        output_path=str(tmp_path / "final_result.txt"),
        chunk_bytes=512,          # many small chunks
        batch_size=256,           # many small device batches
        key_capacity=4096,
        backend="cpu",
        use_native=False,
        **overrides,
    )
    mapper, reducer = make_wordcount(cfg.tokenizer, cfg.use_native)
    return cfg, run_wordcount_job(cfg, mapper, reducer)


def test_wordcount_matches_reference_model(corpus_file, tmp_path):
    cfg, result = _run(corpus_file, tmp_path)
    model = wordcount_model(iter_chunks(corpus_file, 512))
    assert result.counts == dict(model)
    assert result.top == top_k_model(model, 10)


def test_round_robin_compat_chunking_same_result(corpus_file, tmp_path):
    """Byte-range chunking and the reference's round-robin line chunking
    (main.rs:36-51) must produce identical global counts."""
    _, streamed = _run(corpus_file, tmp_path)
    _, rr = _run(corpus_file, tmp_path, num_chunks=8)
    assert streamed.counts == rr.counts
    chunks = split_round_robin(corpus_file, 8)
    assert wordcount_model(chunks) == streamed.counts


def test_final_result_file_deterministic_and_truncated(corpus_file, tmp_path):
    out = tmp_path / "final_result.txt"
    # pre-existing longer file would expose the reference's no-truncate bug
    # (main.rs:171-175): stale trailing bytes must NOT survive.
    out.write_bytes(b"x" * 1_000_000)
    _, result = _run(corpus_file, tmp_path)
    first = out.read_bytes()
    assert len(first) < 1_000_000
    _, result2 = _run(corpus_file, tmp_path)
    assert out.read_bytes() == first  # byte-identical across runs
    # file content round-trips to the counts dict
    parsed = {}
    for line in first.splitlines():
        w, c = line.rsplit(b" ", 1)
        parsed[w] = int(c)
    assert parsed == result.counts


def test_unicode_tokenizer_mode(tmp_path):
    p = tmp_path / "u.txt"
    p.write_bytes("Ärger straße Ärger ÉCLAIR\n".encode("utf-8"))
    cfg = JobConfig(input_path=str(p), output_path="", backend="cpu",
                    tokenizer="unicode", use_native=False,
                    batch_size=64, key_capacity=64)
    mapper, reducer = make_wordcount("unicode", use_native=False)
    result = run_wordcount_job(cfg, mapper, reducer)
    assert result.counts["ärger".encode()] == 2
    assert result.counts["éclair".encode()] == 1
    assert result.counts["straße".encode()] == 1


def test_conservation_metric(corpus_file, tmp_path):
    _, result = _run(corpus_file, tmp_path)
    assert result.metrics["records_in"] == sum(result.counts.values())
    assert result.metrics["distinct_keys"] == len(result.counts)


def test_cli_smoke(corpus_file, tmp_path, capsys, monkeypatch):
    monkeypatch.chdir(tmp_path)
    from map_oxidize_tpu.cli import main

    rc = main(["wordcount", corpus_file, "--backend", "cpu", "--no-native",
               "--top-k", "5", "--output", str(tmp_path / "out.txt"), "-q"])
    assert rc == 0
    out = capsys.readouterr().out
    assert out.startswith("Top 5 words:")
    assert len(out.strip().splitlines()) == 6
    model = wordcount_model([open(corpus_file, "rb").read()])
    for line, (w, c) in zip(out.strip().splitlines()[1:], top_k_model(model, 5)):
        assert line == f"{w.decode()}: {c}"


# --- the program's own spans (the per-layer host timings) -----------------

#: the consumer-side spans of a word-count job, by layer: set-up, feed,
#: audit, device put and dispatch, host blocked on the device, finish
LAYER_SPANS = ("job/setup", "feed/dictionary", "audit/fold_in",
               "engine/feed_block", "engine/flush", "engine/put",
               "engine/dispatch", "engine/health_sync",
               "engine/growth_sync", "xprof/ready_wait", "audit/fold_out",
               "job/finish")
#: the spans on the map+reduce phase's driver thread, with the wait on
#: the map pipeline (``<name>/feed_wait``: ``map/`` on the native scan,
#: ``read/`` on the Python mapper's)
CONSUMER_SPANS = ("feed/dictionary", "audit/fold_in",
                  "engine/feed_block", "engine/flush", "engine/put",
                  "engine/dispatch", "engine/health_sync",
                  "engine/growth_sync", "xprof/ready_wait")


def _growing_job(tmp_path, **overrides):
    """Distinct words keep arriving, so the accumulator grows after its
    first merge (a growth sync) and the merges pass the health-check
    cadence (a health sync every 64)."""
    p = tmp_path / "grow.txt"
    p.write_bytes(" ".join(f"w{i}" for i in range(24000)).encode() + b"\n")
    cfg = JobConfig(input_path=str(p), output_path=str(tmp_path / "o.txt"),
                    chunk_bytes=2048, batch_size=256, initial_key_capacity=64,
                    key_capacity=1 << 15, backend="cpu", use_native=False,
                    metrics=False, **overrides)
    mapper, reducer = make_wordcount(cfg.tokenizer, cfg.use_native)
    return run_wordcount_job(cfg, mapper, reducer)


def test_job_metrics_carry_every_layer_span(tmp_path):
    m = _growing_job(tmp_path).metrics
    for name in LAYER_SPANS:
        assert m[f"span/{name}_n"] >= 1, name
        assert 0.0 <= m[f"span/{name}_self_s"] <= m[f"span/{name}_s"], name
    assert m["span/job/setup_n"] == m["span/job/finish_n"] == 1
    assert m["span/audit/fold_in_n"] == m["chunks"]
    assert m["engine/health_sync_ms/count"] == m["span/engine/health_sync_n"]
    assert m["engine/growth_sync_ms/count"] == m["span/engine/growth_sync_n"]


def _self_times_by_phase(events):
    """Self time (duration less direct children) of each span on the
    driver's thread, summed by (phase, name), from the Chrome events: the
    nesting is time containment on one thread, as Perfetto draws it."""
    phases = [e for e in events if e["name"].startswith("phase/")]
    tid = phases[0]["tid"]
    xs = sorted((e for e in events if e["tid"] == tid),
                key=lambda e: (e["ts"], -e["dur"]))
    out: dict = {}
    stack: list = []   # [event, children's duration, its phase]
    eps = 0.01         # us: the export rounds to 1 ns

    def close(entry):
        e, kids, phase = entry
        key = (phase, e["name"])
        out[key] = out.get(key, 0.0) + (e["dur"] - kids) * 1e-6

    for e in xs:
        while stack and e["ts"] + e["dur"] > (stack[-1][0]["ts"]
                                              + stack[-1][0]["dur"] + eps):
            close(stack.pop())
        if stack:
            stack[-1][1] += e["dur"]
        phase = (e["name"] if e["name"].startswith("phase/")
                 else stack[-1][2] if stack else None)
        stack.append([e, 0.0, phase])
    while stack:
        close(stack.pop())
    return out


def test_consumer_self_times_fit_in_the_map_reduce_wall(tmp_path):
    r = _growing_job(tmp_path, trace_out="-")
    m = r.metrics
    by = _self_times_by_phase([e for e in r.trace if e["ph"] == "X"])
    # the sink's self times are the event list's, span by span
    for name in LAYER_SPANS:
        got = sum(v for (_, n), v in by.items() if n == name)
        assert got == pytest.approx(m[f"span/{name}_self_s"], abs=1e-5), name
    wall = m["span/phase/map+reduce_s"]
    names = {n for ph, n in by if ph == "phase/map+reduce"} - {
        "phase/map+reduce"}
    assert all(n in CONSUMER_SPANS or n.endswith("/feed_wait")
               for n in names), names
    inside = sum(by[("phase/map+reduce", n)] for n in names)
    assert inside <= wall
    # and with the phase's own self time they are the whole of it
    assert inside + m["span/phase/map+reduce_self_s"] == pytest.approx(
        wall, abs=1e-5)


def test_spans_land_in_the_profiler_host_plane(tmp_path):
    """A ``jax.profiler`` capture with the host tracer at level 1 holds
    the program's spans as annotations.  In its own process, under its
    own timeout: a profiler session is process-wide."""
    import os
    import subprocess
    import sys

    script = r'''
import glob, os, sys
import jax
from jax.profiler import ProfileData
from map_oxidize_tpu.config import JobConfig
from map_oxidize_tpu.runtime import run_job
d = sys.argv[1]
p = os.path.join(d, "c.txt")
with open(p, "wb") as f:
    f.write(b"the quick brown fox jumps over the lazy dog\n" * 2000)
cfg = JobConfig(input_path=p, output_path="", num_shards=1, backend="cpu",
                metrics=False, chunk_bytes=4096, batch_size=512)
run_job(cfg, "wordcount")
opts = jax.profiler.ProfileOptions()
opts.python_tracer_level = 0
opts.host_tracer_level = 1
jax.profiler.start_trace(os.path.join(d, "t"), profiler_options=opts)
run_job(cfg, "wordcount")
jax.profiler.stop_trace()
path, = glob.glob(os.path.join(d, "t", "**", "*.xplane.pb"), recursive=True)
for plane in ProfileData.from_file(path).planes:
    if plane.name.startswith("/host:"):
        for line in plane.lines:
            for e in line.events:
                print(e.name)
'''
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join(
                   [root] + [q for q in [os.environ.get("PYTHONPATH")] if q]))
    p = subprocess.run([sys.executable, "-c", script, str(tmp_path)],
                       cwd=root, env=env, capture_output=True, text=True,
                       timeout=240)
    assert p.returncode == 0, p.stderr[-2000:]
    names = set(p.stdout.split("\n"))
    for span in ("audit/fold_in", "engine/flush", "phase/map+reduce",
                 "job/finish"):
        assert span in names, span


def test_round_robin_needs_no_chunk_bytes(corpus_file, tmp_path):
    """``num_chunks`` alone configures the round-robin split: with
    ``chunk_bytes`` 0 the job writes what it writes with a chunk size."""
    out = tmp_path / "final_result.txt"
    _, sized = _run(corpus_file, tmp_path, num_chunks=8)
    want = out.read_bytes()
    out.unlink()
    cfg = JobConfig(input_path=corpus_file, output_path=str(out),
                    chunk_bytes=0, num_chunks=8, batch_size=256,
                    key_capacity=4096, backend="cpu", use_native=False)
    mapper, reducer = make_wordcount(cfg.tokenizer, cfg.use_native)
    res = run_wordcount_job(cfg, mapper, reducer)
    assert out.read_bytes() == want
    assert res.metrics["chunks"] == sized.metrics["chunks"] == 8

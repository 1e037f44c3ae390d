"""One run of one benchmark cell.

``python3 bench/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` runs here.  Everything a cell needs is found by name:

* the cell in ``BENCHMARK.json`` ``workloads`` names a configuration and
  a traffic mix;
* the configuration's file (``configs[].file``) names its job kind, and
  the job kind is ``bench/jobs/<job>.py``: data generator, plain
  reference, one job through the program, and the comparison;
* the traffic mix is ``bench/traffic/<traffic>.json``, parameters that
  the one generator (``benchlib/traffic.py``) reads: when jobs are
  offered, and how much of the window a traced run captures;
* each metric, end to end or per layer, is ``bench/metrics/<name>.py``,
  whose ``read(run)`` takes a :class:`RunView` and returns a number or
  None.  No metric or traffic is named in this file.

A run: JAX on the cell's chips (no TPU, or fewer chips than the cell
asks for, is an error), the data made from the seed, one warm-up job on
the cell's own input (set-up ends there), then the jobs the traffic
offers until ``--seconds`` have passed.  After the window the device's
peak memory is read, and every job of the window is compared with the
plain reference.  The last line of standard output is the result.
"""

from __future__ import annotations

import argparse
import glob
import importlib.util
import json
import os
import shutil
import sys
import time

from benchlib.traffic import Offer, TrafficError

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(BENCH)
MiB = 1 << 20

#: name of a job in the trace's breakdown
JOB_MARK = "bench/job"


def profile_options():
    """The device's trace alone: no Python tracer, no host tracer.  The
    host tracer records every chunk of the host-side transposes of a
    put (2.6M events a k-means job, 6x its put time, PR 22); the jobs'
    bounds come from the harness's own clock instead."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 0
    return opts


class HarnessError(RuntimeError):
    """The run cannot be made as the cell asks (no chip, no program)."""


def load_module(path: str, name: str):
    """Import a file of the benchmark by its path."""
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise HarnessError(f"cannot load {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


class Cell:
    """A cell of ``BENCHMARK.json`` with its configuration and traffic."""

    def __init__(self, name: str, benchmark: dict | None = None):
        bm = benchmark or load_json(os.path.join(REPO, "BENCHMARK.json"))
        cells = {w["name"]: w for w in bm["workloads"]}
        if name not in cells:
            raise HarnessError(f"no cell {name!r} in BENCHMARK.json")
        self.name = name
        self.entry = cells[name]
        self.chips = int(self.entry["chips"])
        conf = {c["name"]: c for c in bm["configs"]}[self.entry["config"]]
        self.config = load_json(os.path.join(REPO, conf["file"]))
        self.config_name = conf["name"]
        self.traffic = load_json(os.path.join(
            BENCH, "traffic", self.entry["traffic"] + ".json"))
        Offer(self.traffic, 0, 1.0)     # refuse an unknown mix before a run
        self.end_to_end = [m for m in bm["end_to_end"]
                           if name in m.get("workloads", [name])]
        self.per_layer = [m for m in bm["per_layer"]
                          if name in m.get("workloads", [name])]


class RunView:
    """What a metric's reader sees: the window's jobs and what the
    harness measured around them."""

    def __init__(self, config: dict, records: list[dict],
                 window_s: float | None = None, setup_s: float | None = None,
                 trace=None, traced_jobs: int = 0,
                 window_compiles: int | None = None,
                 peaks: dict | None = None):
        #: the configuration's file, as run
        self.config = config
        #: every job of the window, in order: ``arrival_s``, ``start_s``,
        #: ``end_s`` (host clock, seconds from the window's start),
        #: ``input_bytes``, ``failed``, and the job's own keys
        self.records = records
        #: job metrics (``JobResult.metrics``) of the jobs that returned
        self.jobs = [r["metrics"] for r in records if not r.get("failed")]
        #: the window, seconds: end of set-up to the end of its last job
        self.window_s = window_s
        self.setup_s = setup_s
        #: :class:`benchlib.trace.Reduction` of the traced stretch
        self.trace = trace
        self.traced_jobs = traced_jobs
        self.window_compiles = window_compiles
        self.peaks = peaks

    def mean_job(self, key: str, scale: float = 1.0) -> float | None:
        """Mean of a job metric over the window's jobs, None where no
        job reports it."""
        vals = [float(j[key]) for j in self.jobs if j.get(key) is not None]
        return scale * sum(vals) / len(vals) if vals else None


def read_metrics(entries: list[dict], view: RunView) -> dict:
    """Each entry's value by its reader ``bench/metrics/<name>.py``; a
    reader that finds nothing leaves its metric out."""
    out = {}
    for m in entries:
        reader = load_module(os.path.join(BENCH, "metrics", m["name"] + ".py"),
                             "bench_metric_" + m["name"].replace(".", "_"))
        v = reader.read(view)
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


def parse_args(argv):
    ap = argparse.ArgumentParser(description="one run of one bench cell")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def open_devices(chips: int, require_tpu: bool):
    """The cell's devices; a missing TPU or too few chips is an error."""
    import jax

    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise HarnessError(f"no TPU: JAX found {devs[0].platform!r}")
    if len(devs) < chips:
        raise HarnessError(f"the cell needs {chips} chips, JAX sees "
                           f"{len(devs)}")
    return devs


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             t_start: float, require_tpu: bool = True,
             backend: str = "tpu", log=None, data_root: str | None = None
             ) -> dict:
    """Set up, warm up, run the window, compare; return the result."""
    from benchlib.compiles import CompileCounter
    from benchlib.peaks import peaks_for

    log = log or (lambda msg: print(msg, file=sys.stderr, flush=True))
    counter = CompileCounter().install()
    devs = open_devices(cell.chips, require_tpu)
    log(f"{len(devs)} {devs[0].platform} devices after "
        f"{time.perf_counter() - t_start:.3f} s")
    peaks = peaks_for(devs[0].device_kind) if require_tpu else None
    kind = cell.config["job"]
    jobmod = load_module(os.path.join(BENCH, "jobs", kind + ".py"),
                         f"bench_job_{kind}")
    workdir = os.path.join(data_root or os.path.join(BENCH, ".data"),
                           f"{cell.config_name}-{seed}")
    shutil.rmtree(workdir, ignore_errors=True)
    job = jobmod.Job(cell.config, seed, workdir, backend=backend)
    try:
        return _run(cell, job, seconds, trace, t_start, counter, devs, peaks,
                    workdir, log)
    finally:
        shutil.rmtree(workdir, ignore_errors=True)


def _run(cell, job, seconds, trace, t_start, counter, devs, peaks, workdir,
         log) -> dict:
    import jax

    t0 = time.perf_counter()
    job.setup()
    log(f"data made in {time.perf_counter() - t0:.3f} s")
    failed = 0
    t0 = time.perf_counter()
    try:
        job.run(-1)                             # warm-up on the cell's input
    except Exception as e:  # the program's fault: the run is not correct
        log(f"warm-up job failed: {type(e).__name__}: {e}")
        failed += 1
    setup_s = time.perf_counter() - t_start
    log(f"warm-up job {time.perf_counter() - t0:.3f} s; set-up {setup_s:.3f} s")

    offer = Offer(cell.traffic, job.seed, seconds)
    trace_dir = os.path.join(workdir, "trace")
    tracing = False
    traced: list[tuple[float, float, str]] = []  # ns from start_trace
    trace_t0 = 0.0
    trace_done = not trace
    records: list[dict] = []
    compiles_before = counter.count
    w0 = time.perf_counter()
    now = w0
    while True:
        i = len(records)
        arrival = offer.at(i, now - w0)
        if arrival is None and not trace_done and offer.kind == "closed":
            arrival = now - w0      # a closed loop runs on for the trace
        if arrival is None:
            break
        time.sleep(max(0.0, arrival - (time.perf_counter() - w0)))
        if trace and not tracing and not trace_done and i >= 1:
            # a short steady stretch: from the window's second job on
            trace_t0 = time.perf_counter()
            jax.profiler.start_trace(trace_dir,
                                     profiler_options=profile_options())
            tracing = True
        t_job = time.perf_counter()
        try:
            rec = job.run(i)
        except Exception as e:  # a failed job counts, the loop goes on
            log(f"job {i} failed: {type(e).__name__}: {e}")
            rec = {"failed": True, "metrics": {}}
            failed += 1
        now = time.perf_counter()
        rec.setdefault("input_bytes", job.input_bytes)
        rec.update(arrival_s=arrival, start_s=t_job - w0, end_s=now - w0)
        records.append(rec)
        if tracing:
            traced.append(((t_job - trace_t0) * 1e9, (now - trace_t0) * 1e9,
                           f"{JOB_MARK} {i}"))
            if (len(traced) >= offer.trace_min_jobs and
                    traced[-1][1] - traced[0][0] >= offer.trace_min_s * 1e9):
                jax.profiler.stop_trace()
                tracing, trace_done = False, True
    if tracing:
        jax.profiler.stop_trace()
    window_s = now - w0
    window_compiles = counter.count - compiles_before
    log("job walls (s): " + json.dumps(
        [round(r["end_s"] - r["start_s"], 4) for r in records]))

    memory_peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                      for d in devs[:cell.chips])
    ok_records = [r for r in records if not r.get("failed")]
    checks = job.check(records)
    correct = failed == 0 and bool(ok_records) and all(
        v <= lim for _, v, lim in checks)

    result_device = {"platform": devs[0].platform,
                     "kind": devs[0].device_kind, "count": len(devs),
                     "memory_peak_bytes": memory_peak}
    breakdown = None
    if not trace:
        metrics = read_metrics(cell.end_to_end, RunView(
            cell.config, records, window_s=window_s, setup_s=setup_s))
    else:
        from benchlib.trace import reduce_trace

        red = reduce_trace(_xplane(trace_dir), cell.chips, traced)
        log("trace modules (s): " + json.dumps(dict(sorted(
            red.module_s.items(), key=lambda kv: -kv[1])[:20])))
        log("trace busy s per chip: " + json.dumps(red.busy_by_device))
        metrics = read_metrics(cell.per_layer, RunView(
            cell.config, records, window_s=window_s, setup_s=setup_s,
            trace=red, traced_jobs=len(traced),
            window_compiles=window_compiles, peaks=peaks))
        result_device.update(busy_s=red.busy_s, window_s=red.window_s)
        breakdown = red.breakdown()
    result = {"correct": correct, "attempted": len(records),
              "failed": sum(1 for r in records if r.get("failed")),
              "metrics": metrics, "device": result_device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    log(f"window {window_s:.3f} s, {len(records)} jobs, {failed} failed, "
        f"{window_compiles} compiles in the window")
    result["checks"] = {name: {"value": v, "limit": lim}
                        for name, v, lim in checks}
    return result


def _xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise HarnessError(f"the profiler wrote no trace under {trace_dir}")
    return found[-1]


def main(argv, t_start: float) -> int:
    args = parse_args(argv)
    sys.path.insert(0, REPO)
    try:
        cell = Cell(args.workload)
        from map_oxidize_tpu.utils.jax_compat import configure_compile_cache
    except (HarnessError, TrafficError, ImportError, OSError, KeyError) as e:
        print(f"FAIL: {type(e).__name__}: {e}", file=sys.stderr)
        return 1
    configure_compile_cache()
    try:
        result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                          t_start)
    except (HarnessError, KeyError) as e:
        print(f"FAIL: {e}", file=sys.stderr)
        return 1
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0

"""XLA program observatory: cost/roofline join, report rendering, and the
live device sampler.

The join half turns :mod:`map_oxidize_tpu.obs.compile`'s raw per-program
record (compiles, causes, FLOPs/bytes from ``cost_analysis``, dispatch
timings) into the per-job accounting the ISSUE's Exoshuffle argument
demands — *where the FLOPs and bytes actually go*:

* achieved FLOP/s and bytes/s per program, from cost-analysis cost x
  dispatch count over the estimated device time (the sampled
  ``block_until_ready`` waits when available, else the dispatch walls);
* MFU against the device's published peak (:data:`PEAKS`, keyed by
  ``device_kind``; ``MOXT_PEAK_FLOPS`` overrides, e.g. with bench's
  measured matmul probe) and achieved-bandwidth fraction against its
  published HBM rate (``MOXT_PEAK_MEMBW`` overrides);
* a memory-bound / compute-bound classification from arithmetic
  intensity vs the machine balance point.

The sampler half is one low-rate daemon thread per job doing two things
the inline instrumentation cannot:

* **live HBM watermarks** — ``hbm/live_bytes_device<i>`` gauges sampled
  from ``device.memory_stats()`` between phase boundaries (the existing
  end-of-phase samples miss mid-phase peaks), surfaced on heartbeat
  lines and in flight-recorder crash bundles;
* **stall detection** — if no chunk completes within a configurable
  multiple of the median inter-chunk interval, one ``[stalled]`` line
  names the currently open span stacks (exactly what a hung feed loop
  or a wedged collective looks like from the outside).
"""

from __future__ import annotations

import os
import sys
import threading
import time

from map_oxidize_tpu.utils.logging import get_logger

_log = get_logger(__name__)

#: published per-chip peaks, keyed by ``device_kind`` as JAX reports it.
#: Source: Google Cloud documentation, "TPU v5e" (197 TFLOP/s bf16,
#: 16 GB HBM at 819 GB/s).  A TPU whose kind is missing here is an error,
#: not a default: an MFU quoted against another part's peak is wrong.
PEAKS = {
    "TPU v5 lite": {"flops": 197e12, "membw": 819e9},
}

#: machine-balance fallback (FLOPs per byte) for the bound classification
#: when no peak pair is known (CPU): the v5e ratio above, rounded
DEFAULT_BALANCE = 240.0


def peaks_for(device) -> dict | None:
    """The published peaks of ``device``: None on CPU (MFU means nothing
    there), a ``KeyError`` for a TPU kind the table does not hold."""
    if device.platform != "tpu":
        return None
    try:
        return PEAKS[device.device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device_kind {device.device_kind!r}; "
            "add them to map_oxidize_tpu.obs.xprof.PEAKS") from None


def device_peaks(backend: str = "auto") -> dict:
    """The peak rates MFU is quoted against.  ``MOXT_PEAK_FLOPS`` /
    ``MOXT_PEAK_MEMBW`` env overrides win PER FIELD (bench exports only
    its measured matmul peak — the membw figure must survive that);
    whatever the env leaves unset comes from :data:`PEAKS` for the
    running device.  ``backend="tpu"`` without a TPU raises."""
    peaks = {"flops": None, "membw": None, "source": "none"}
    if sys.modules.get("jax") is not None or backend == "tpu":
        import jax

        dev = jax.devices()[0]
        if backend == "tpu" and dev.platform != "tpu":
            raise RuntimeError(
                f"backend='tpu' but the default device is {dev.platform!r}")
        table = peaks_for(dev)
        if table is not None:
            peaks.update(table, source=f"published:{dev.device_kind}")
    env_used = False
    for env, key in (("MOXT_PEAK_FLOPS", "flops"),
                     ("MOXT_PEAK_MEMBW", "membw")):
        v = os.environ.get(env)
        if v:
            try:
                peaks[key] = float(v)
                env_used = True
            except ValueError:
                pass
    if env_used:
        peaks["source"] = ("env" if peaks["source"] == "none"
                           else f"env+{peaks['source']}")
    return peaks


def job_report(delta: dict) -> dict:
    """Join one job's compile-ledger delta (``CompileLedger.job_delta``)
    with the session peaks into the per-program observatory rows the
    metrics document carries (``metrics.json["xprof"]``)."""
    peaks = device_peaks()
    balance = (peaks["flops"] / peaks["membw"]
               if peaks["flops"] and peaks["membw"] else DEFAULT_BALANCE)
    programs = {}
    for name, d in sorted(delta.items()):
        row = dict(d)
        n = d["dispatches"]
        flops = d.get("flops_per_dispatch")
        bytes_ = d.get("bytes_per_dispatch")
        # device-time estimate: mean sampled ready-wait x dispatches when
        # samples exist (the honest figure under async dispatch), else
        # the summed dispatch walls (an upper bound: host overhead rides
        # along, so rates and MFU read conservative)
        dev_s = None
        if d["device_samples"] > 0 and d["sampled_device_ms"] > 0:
            dev_s = (d["sampled_device_ms"] / d["device_samples"]) * n / 1e3
            row["device_time_source"] = "sampled_ready_wait"
        elif d["dispatch_ms"] > 0:
            dev_s = d["dispatch_ms"] / 1e3
            row["device_time_source"] = "dispatch_wall"
        row["device_s_est"] = round(dev_s, 6) if dev_s else None
        # per-LOGICAL-chunk dispatch attribution: a scan-batched program
        # retires B chunks per launch, so its per-dispatch gap is not
        # comparable across B — gap / logical chunks is (the number the
        # dispatch-floor trajectory tracks)
        ch = d.get("logical_chunks") or 0
        if ch and d["dispatch_ms"] > 0:
            row["chunks_per_dispatch"] = round(
                ch / max(n - d["compiles"], 1), 2)
            row["dispatch_gap_per_chunk_ms"] = round(
                d["dispatch_ms"] / ch, 4)
        if n and flops and dev_s:
            row["achieved_flops_per_s"] = round(flops * n / dev_s, 1)
            if peaks["flops"]:
                row["mfu_pct"] = round(
                    100.0 * flops * n / dev_s / peaks["flops"], 3)
        if n and bytes_ and dev_s:
            row["achieved_bytes_per_s"] = round(bytes_ * n / dev_s, 1)
            if peaks["membw"]:
                row["membw_pct"] = round(
                    100.0 * bytes_ * n / dev_s / peaks["membw"], 3)
        if flops and bytes_:
            intensity = flops / bytes_
            row["intensity_flops_per_byte"] = round(intensity, 4)
            row["bound"] = ("compute" if intensity >= balance else "memory")
        programs[name] = row
    return {
        "programs": programs,
        "peaks": peaks,
        "balance_flops_per_byte": round(balance, 2),
        "total_compiles": sum(d["compiles"] for d in delta.values()),
        "total_compile_ms": round(
            sum(d["compile_ms"] for d in delta.values()), 3),
        "total_dispatches": sum(d["dispatches"] for d in delta.values()),
    }


def flatten_report(report: dict) -> dict:
    """The scalar projection of :func:`job_report` for the flat metrics
    summary — what rides ``JobResult.metrics``, the run ledger, and the
    ``obs diff --gate`` / ``bench.py --gate`` regression checks."""
    out = {
        "compile/total_compiles": report["total_compiles"],
        "compile/total_ms": report["total_compile_ms"],
    }
    for name, row in report["programs"].items():
        out[f"compile/{name}/compiles"] = row["compiles"]
        out[f"compile/{name}/shape_sets"] = row["shape_sets"]
        if row["recompile_causes"]:
            out[f"compile/{name}/recompile_cause"] = \
                row["recompile_causes"][-1]
        out[f"xprof/{name}/dispatches"] = row["dispatches"]
        for k, dst in (("mfu_pct", "mfu_pct"), ("membw_pct", "membw_pct"),
                       ("bound", "bound")):
            if row.get(k) is not None:
                out[f"xprof/{name}/{dst}"] = row[k]
        # per-logical-chunk attribution (an unbatched program retires 1
        # chunk/dispatch, so for it this equals the mean dispatch gap —
        # the value stays comparable when the same program later batches)
        if row.get("dispatch_gap_per_chunk_ms") is not None:
            out[f"xprof/{name}/logical_chunks"] = row["logical_chunks"]
            out[f"xprof/{name}/dispatch_gap_per_chunk_ms"] = \
                row["dispatch_gap_per_chunk_ms"]
    return out


# --- report rendering (the `obs xprof` table) ------------------------------


def _fmt_rate(v, unit):
    if v is None:
        return "-"
    for scale, suffix in ((1e12, "T"), (1e9, "G"), (1e6, "M"), (1e3, "K")):
        if v >= scale:
            return f"{v / scale:.2f} {suffix}{unit}"
    return f"{v:.1f} {unit}"


def render_report(report: dict, histograms: dict | None = None) -> str:
    """Human-readable observatory report: the compile table, the
    cost/utilization table, and the dispatch-gap histogram summary."""
    lines = ["XLA program observatory"]
    peaks = report.get("peaks", {})
    lines.append(
        f"  peaks: flops={_fmt_rate(peaks.get('flops'), 'FLOP/s')} "
        f"membw={_fmt_rate(peaks.get('membw'), 'B/s')} "
        f"({peaks.get('source', '?')}); balance "
        f"{report.get('balance_flops_per_byte')} FLOP/byte")
    progs = report.get("programs", {})
    if not progs:
        lines.append("  (no observed programs ran in this job)")
        return "\n".join(lines)
    lines.append(
        f"  {report['total_compiles']} compiles "
        f"({report['total_compile_ms']:.1f} ms) across {len(progs)} "
        f"programs, {report['total_dispatches']} dispatches")
    lines.append("compiles:")
    lines.append(f"  {'program':<28} {'n':>3} {'ms':>9} {'shapes':>6}  cause")
    for name, r in progs.items():
        cause = ", ".join(r["recompile_causes"]) if r["recompile_causes"] \
            else "-"
        lines.append(f"  {name:<28} {r['compiles']:>3} "
                     f"{r['compile_ms']:>9.1f} {r['shape_sets']:>6}  {cause}")
    lines.append("cost / utilization:")
    lines.append(f"  {'program':<28} {'disp':>5} {'flops/disp':>11} "
                 f"{'bytes/disp':>11} {'achieved':>12} {'MFU%':>6} "
                 f"{'bw%':>6}  bound")
    for name, r in progs.items():
        lines.append(
            f"  {name:<28} {r['dispatches']:>5} "
            f"{_fmt_rate(r.get('flops_per_dispatch'), ''):>11} "
            f"{_fmt_rate(r.get('bytes_per_dispatch'), ''):>11} "
            f"{_fmt_rate(r.get('achieved_flops_per_s'), 'F/s'):>12} "
            f"{r.get('mfu_pct', '-'):>6} {r.get('membw_pct', '-'):>6}  "
            f"{r.get('bound', '-')}")
    if histograms:
        for h in ("device/dispatch_gap_ms",
                  "device/dispatch_gap_per_chunk_ms", "device/compute_ms"):
            s = histograms.get(h)
            if s:
                lines.append(
                    f"{h}: n={s.get('count')} p50={s.get('p50')} "
                    f"p95={s.get('p95')} max={s.get('max')} "
                    f"mean={s.get('mean')}")
    return "\n".join(lines)


# --- live device sampler ---------------------------------------------------


class DeviceSampler:
    """Low-rate daemon thread: live HBM watermarks + the stall detector.

    Chunk progress is read from the job's own registry (the
    ``feed_block_ms`` / ``device/dispatch_gap_ms`` histogram counts and
    the ``engine/flushes`` / ``pipeline/chunks`` counters), so the
    detector needs no extra hooks in the drivers and works with or
    without ``--progress``.  Stall warnings fire once per episode (a
    completing chunk re-arms the detector).
    """

    #: registry series whose growth means "a chunk completed"
    PROGRESS_HISTS = ("feed_block_ms", "device/dispatch_gap_ms")
    PROGRESS_COUNTERS = ("engine/flushes", "pipeline/chunks")

    def __init__(self, obs, interval_s: float = 0.0,
                 stall_factor: float = 0.0):
        self.obs = obs
        self.interval_s = interval_s if interval_s > 0 else 0.5
        self.stall_factor = stall_factor
        self.sample_hbm = interval_s > 0
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="obs-device-sampler")
        self._intervals: list[float] = []
        self._last_signal = 0
        self._last_change = time.monotonic()
        self._warned = False
        self.stall_warnings = 0

    def start(self) -> None:
        self._thread.start()

    def stop(self) -> None:
        self._stop.set()
        # final watermark read so short jobs still record one sample
        if self.sample_hbm:
            self.sample_once()

    def _run(self) -> None:
        while not self._stop.wait(self.interval_s):
            if self.sample_hbm:
                self.sample_once()
            if self.stall_factor > 0:
                self.check_stall()

    # --- HBM --------------------------------------------------------------

    def sample_once(self) -> None:
        """One live-bytes reading per initialized device.  A no-op until
        the job itself has imported jax (never init a backend from the
        sampler) and on backends without memory stats (CPU)."""
        jax = sys.modules.get("jax")
        if jax is None:
            return
        try:
            devices = jax.devices()
        except Exception:
            return
        best = None
        for d in devices:
            try:
                stats = d.memory_stats()
            except Exception:
                stats = None
            if not stats:
                continue
            in_use = stats.get("bytes_in_use")
            if in_use is None:
                continue
            self.obs.registry.gauge_max(f"hbm/live_bytes_device{d.id}",
                                        int(in_use))
            best = max(best or 0, int(in_use))
        if best is not None and self.obs.heartbeat is not None:
            self.obs.heartbeat.hbm_bytes = best

    # --- stall detection --------------------------------------------------

    def _progress_signal(self) -> int:
        reg = self.obs.registry
        with reg._lock:
            n = sum(reg.histograms[h].count for h in self.PROGRESS_HISTS
                    if h in reg.histograms)
            n += sum(int(reg.counters.get(c, 0))
                     for c in self.PROGRESS_COUNTERS)
        return n

    def check_stall(self, now: float | None = None) -> bool:
        """One detector tick (public for the fake-clock tests).  Returns
        True when a stall warning was emitted this tick."""
        now = time.monotonic() if now is None else now
        sig = self._progress_signal()
        if sig != self._last_signal:
            if self._last_signal:
                self._intervals.append(now - self._last_change)
                if len(self._intervals) > 64:
                    del self._intervals[0]
            self._last_signal = sig
            self._last_change = now
            self._warned = False
            return False
        if self._warned or len(self._intervals) < 3:
            return False
        med = sorted(self._intervals)[len(self._intervals) // 2]
        elapsed = now - self._last_change
        if med <= 0 or elapsed < self.stall_factor * med:
            return False
        self._warned = True
        self.stall_warnings += 1
        tracer = self.obs.tracer
        spans = []
        with tracer._lock:
            for _tid, stack in tracer._stacks:
                if stack:
                    spans.append(" > ".join(s.name for s in stack))
        open_s = "; ".join(spans) if spans else "(none)"
        line = (f"[stalled] no chunk completed in {elapsed:.1f}s "
                f"(median {med:.2f}s, factor {self.stall_factor:g}); "
                f"open spans: {open_s}")
        hb = self.obs.heartbeat
        if hb is not None and not getattr(hb, "silent", False):
            hb._emit(line)
        else:
            # no heartbeat, or a silent tracking-only one (the live
            # plane's /status feed): the warning must still hit the log
            _log.warning("%s", line)
        # the counter the ledger gate and /status read: a stall episode
        # is evidence, not just a log line (any increase vs the previous
        # comparable run flags in `obs diff --gate`)
        self.obs.registry.count("heartbeat/stalls")
        return True

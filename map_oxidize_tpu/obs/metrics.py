"""Counters, gauges, and lightweight histograms behind the seed
``Metrics`` surface.

The seed's ``utils.profiling.Metrics`` was a flat phase-timer/counter
dict only the driver read.  :class:`MetricsRegistry` keeps that exact
surface (``phase`` / ``count`` / ``set`` / ``summary``) so every existing
consumer — bench.py, the spill tests, the CLI metrics log line — works
unchanged, and adds what the scale targets need:

* **counters** — monotonically accumulated (rows fed, spill bytes,
  all_to_all payload bytes, demotion events);
* **gauges** — last-value or watermark (``gauge_max``) readings
  (host-RSS peak, HBM in use, registers filled);
* **histograms** — p50/p95/max over per-event observations (per-block
  feed latency, flush latency) with bounded memory: an exact
  count/mean/min/max plus a deterministic stride-decimated sample set
  for the quantiles.

Gauge names are slash-namespaced by owning subsystem — ``spill/*``,
``shuffle/*``, ``hbm/*``, ``critpath/*``, ``fleet/*``, and ``data/*``
(the data-plane observatory: ``data/imbalance_factor``,
``data/reduction_ratio``, ``data/conservation_violations``, ... — see
:mod:`map_oxidize_tpu.obs.dataplane`).  The ledger diff gates, the SLO
evaluator, the series ring, and ``/status`` all key off these names.

All mutating entry points take one lock; contention is negligible at the
per-chunk/per-flush cadence the hot paths record at.
"""

from __future__ import annotations

import bisect
import contextlib
import threading
import time

#: default cumulative-bucket bounds for latency histograms, in ms —
#: 5ms..10min, roughly log-spaced (the serve job-latency SLO metrics:
#: queue wait, admission wait, run wall)
LATENCY_BUCKETS_MS = (
    5.0, 10.0, 25.0, 50.0, 100.0, 250.0, 500.0, 1000.0, 2500.0, 5000.0,
    10_000.0, 30_000.0, 60_000.0, 120_000.0, 300_000.0, 600_000.0)


class Histogram:
    """Streaming summary of one observation series.

    Exact ``count``/``sum``/``min``/``max``; quantiles come from a
    deterministic sample: every ``stride``-th observation is kept, and
    when the kept set reaches ``max_samples`` it is decimated 2:1 and the
    stride doubles — bounded memory, no RNG (reproducible runs), and the
    sample stays uniformly spread over the series.

    ``buckets`` (a sorted sequence of upper bounds) additionally keeps
    exact fixed-bucket counts, so the histogram can export as a REAL
    cumulative-bucket Prometheus histogram (``_bucket{le=...}``) — the
    shape burn-rate/quantile queries need on a stock scraper, which the
    decimated-sample summary quantiles cannot provide.  The serve-plane
    job-latency histograms use :data:`LATENCY_BUCKETS_MS`.
    """

    __slots__ = ("count", "total", "min", "max", "_samples", "_stride",
                 "_max_samples", "buckets", "bucket_counts")

    def __init__(self, max_samples: int = 8192, buckets=None):
        self.count = 0
        self.total = 0.0
        self.min: float | None = None
        self.max: float | None = None
        self._samples: list[float] = []
        self._stride = 1
        self._max_samples = max_samples
        #: fixed upper bounds for the cumulative-bucket export (an
        #: implicit +Inf overflow bucket rides at the end); None = the
        #: summary-only histogram every existing site creates
        self.buckets: tuple | None = (
            tuple(sorted(float(b) for b in buckets)) if buckets else None)
        self.bucket_counts: list[int] | None = (
            [0] * (len(self.buckets) + 1) if self.buckets else None)

    def observe(self, value: float) -> None:
        value = float(value)
        self.count += 1
        self.total += value
        if self.min is None or value < self.min:
            self.min = value
        if self.max is None or value > self.max:
            self.max = value
        if self.buckets is not None:
            self.bucket_counts[bisect.bisect_left(self.buckets,
                                                  value)] += 1
        if self.count % self._stride == 0:
            self._samples.append(value)
            if len(self._samples) >= self._max_samples:
                self._samples = self._samples[1::2]
                self._stride *= 2

    def cumulative_buckets(self) -> list[tuple[float, int]] | None:
        """``(le, cumulative_count)`` pairs ending at ``(+inf, count)``,
        or None for a summary-only histogram."""
        if self.buckets is None:
            return None
        out, acc = [], 0
        for le, n in zip(self.buckets, self.bucket_counts):
            acc += n
            out.append((le, acc))
        out.append((float("inf"), self.count))
        return out

    def quantile(self, q: float) -> float | None:
        if not self._samples:
            return self.max
        s = sorted(self._samples)
        idx = min(int(q * len(s)), len(s) - 1)
        return s[idx]

    def summary(self) -> dict:
        s = {
            "count": self.count,
            "mean": round(self.total / self.count, 6) if self.count else 0.0,
            "p50": _round6(self.quantile(0.50)),
            "p95": _round6(self.quantile(0.95)),
            "max": _round6(self.max),
        }
        if self.buckets is not None:
            s["buckets"] = {
                ("+Inf" if le == float("inf") else f"{le:g}"): n
                for le, n in self.cumulative_buckets()}
        return s


def _round6(v):
    return None if v is None else round(v, 6)


#: spans whose durations also land in a histogram, in ms, under the name
#: its readers (obs/attrib.py, obs/xprof.py, the ledger) know
SPAN_HISTOGRAMS = {
    "engine/feed_block": "feed_block_ms",
    "engine/flush": "engine/flush_ms",
    "engine/health_sync": "engine/health_sync_ms",
    "engine/growth_sync": "engine/growth_sync_ms",
}

#: spans whose durations also add up, in seconds, under the key its
#: readers (bench.py, chip_smoke.py, the benchmark) know
SPAN_SECONDS = {
    "kmeans/put": "time/transfer_s",
    "kmeans/fit": "time/iter_s",
}


def _span_alias(name: str) -> tuple[str | None, str | None]:
    """``(kind, key)`` of the older key a span also writes, if any."""
    if name.startswith("phase/"):
        return "phase", name[len("phase/"):]
    if name.endswith("/feed_wait"):
        # every prefetcher's consumer stall (map/, push/, kmeans/map/...)
        return "counter_ms", "pipeline/feed_wait_ms"
    if name.endswith("/produce"):
        return "counter_ms", "pipeline/produce_ms"
    if name in SPAN_HISTOGRAMS:
        return "hist_ms", SPAN_HISTOGRAMS[name]
    if name in SPAN_SECONDS:
        return "counter_s", SPAN_SECONDS[name]
    return None, None


class MetricsRegistry:
    """Thread-safe registry of phases, counters, gauges, and histograms.

    Drop-in for the seed ``Metrics``: ``phase``/``count``/``set`` keep
    their semantics and ``summary()`` returns the same flat dict shape
    (``time/<phase>_s`` keys, counters/gauges by plain name, the derived
    ``records_per_sec``) plus flattened histogram quantiles.
    """

    def __init__(self):
        self.phases: dict[str, float] = {}
        self.counters: dict[str, float] = {}
        self.gauges: dict[str, float] = {}
        self.histograms: dict[str, Histogram] = {}
        #: comms observatory rows: (collective, program, shape) ->
        #: {count, bytes, latency Histogram} — see :meth:`comm`
        self._comms: dict[tuple, dict] = {}
        #: sticky Prometheus export-name assignments for this registry's
        #: lifetime ((kind, name) -> moxt_* name, plus the taken set):
        #: registry keys are created lazily mid-run, and a later-created
        #: colliding key must NEVER steal an already-exported series'
        #: name (obs/serve.py's exporter owns the population logic)
        self._prom_names: dict = {}
        self._prom_used: set = set()
        #: span name -> its registry keys (:meth:`record_span`)
        self._span_keys: dict[str, tuple] = {}
        self._lock = threading.Lock()

    # --- seed-compatible surface -----------------------------------------

    @contextlib.contextmanager
    def phase(self, name: str):
        t0 = time.perf_counter()
        try:
            yield
        finally:
            dt = time.perf_counter() - t0
            with self._lock:
                self.phases[name] = self.phases.get(name, 0.0) + dt

    def count(self, name: str, delta: float = 1) -> None:
        with self._lock:
            self.counters[name] = self.counters.get(name, 0) + delta

    def set(self, name: str, value) -> None:
        """Record a last-value gauge (the seed's ``set``)."""
        with self._lock:
            self.gauges[name] = value

    # --- new surface ------------------------------------------------------

    gauge = set

    def gauge_max(self, name: str, value: float) -> None:
        """Watermark gauge: keeps the maximum ever recorded (memory
        peaks)."""
        with self._lock:
            if value > self.gauges.get(name, float("-inf")):
                self.gauges[name] = value

    def observe(self, name: str, value: float, buckets=None) -> None:
        """Add one observation to the named histogram (created lazily).
        ``buckets`` (applied at creation) switches the histogram to ALSO
        keep exact cumulative-bucket counts for the Prometheus
        ``_bucket{le=...}`` export — see :class:`Histogram`."""
        with self._lock:
            h = self.histograms.get(name)
            if h is None:
                h = self.histograms[name] = Histogram(buckets=buckets)
            h.observe(value)

    def record_span(self, name: str, dur_s: float, self_s: float) -> None:
        """The span sink (:class:`~map_oxidize_tpu.obs.trace.Tracer`
        ``sink``): every closed span adds its duration, self time and
        count under ``span/<name>_s``, ``span/<name>_self_s`` and
        ``span/<name>_n``, and writes the keys older readers know for the
        same region: ``phase/<p>`` the ``time/<p>_s`` phase clock,
        ``<prefetcher>/produce`` and ``/feed_wait`` the ``pipeline/*_ms``
        counters, the spans of :data:`SPAN_HISTOGRAMS` their
        histograms and those of :data:`SPAN_SECONDS` their ``time/*_s``
        keys."""
        keys = self._span_keys.get(name)
        if keys is None:
            keys = self._span_keys[name] = (
                f"span/{name}_s", f"span/{name}_self_s", f"span/{name}_n",
                _span_alias(name))
        dur_key, self_key, n_key, (kind, alias) = keys
        with self._lock:
            c = self.counters
            c[dur_key] = c.get(dur_key, 0.0) + dur_s
            c[self_key] = c.get(self_key, 0.0) + self_s
            c[n_key] = c.get(n_key, 0) + 1
            if kind == "phase":
                self.phases[alias] = self.phases.get(alias, 0.0) + dur_s
            elif kind == "counter_ms":
                c[alias] = c.get(alias, 0.0) + dur_s * 1e3
            elif kind == "counter_s":
                c[alias] = c.get(alias, 0.0) + dur_s
            elif kind == "hist_ms":
                h = self.histograms.get(alias)
                if h is None:
                    h = self.histograms[alias] = Histogram()
                h.observe(dur_s * 1e3)

    @contextlib.contextmanager
    def timer(self, name: str):
        """Time a block into the named histogram, in milliseconds."""
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.observe(name, (time.perf_counter() - t0) * 1e3)

    # --- comms observatory ------------------------------------------------

    def comm(self, collective: str, program: str, nbytes: float,
             shape=None, latency_ms: float | None = None) -> None:
        """Record one collective invocation: ``collective`` is the
        primitive (``all_to_all`` / ``psum`` / ``all_gather``),
        ``program`` the observed-jit program (or host call site) it runs
        in, ``nbytes`` the global payload the invocation moved (the
        host-side accounting identity — XLA's collectives can't
        self-report), ``shape`` the per-shard buffer shape.  Accumulates
        the per-(collective, program, shape) table the metrics document
        exports (``comms`` section) AND the flat
        ``comms/<collective>/<program>/{bytes,calls}`` counters the run
        ledger and ``obs diff --gate`` compare.  ``latency_ms`` is the
        sampled per-invocation wall where the site measures one (host-
        synchronous collectives every call; async dispatch sites on
        their sampling cadence)."""
        key = (collective, program, _shape_str(shape))
        with self._lock:
            row = self._comms.get(key)
            if row is None:
                row = self._comms[key] = {
                    "count": 0, "bytes": 0.0, "latency": Histogram(1024)}
            row["count"] += 1
            row["bytes"] += nbytes
            if latency_ms is not None:
                row["latency"].observe(latency_ms)
            for name, delta in (
                    (f"comms/{collective}/{program}/bytes", nbytes),
                    (f"comms/{collective}/{program}/calls", 1)):
                self.counters[name] = self.counters.get(name, 0) + delta

    def comms_table(self) -> list[dict]:
        """The per-(collective, program, shape) rows, sorted by bytes
        descending — the measurement substrate ROADMAP open item 5's
        collective chooser consumes."""
        rows = []
        with self._lock:
            for (collective, program, shape), r in self._comms.items():
                lat = (r["latency"].summary() if r["latency"].count
                       else None)
                rows.append({
                    "collective": collective, "program": program,
                    "shape": shape, "count": r["count"],
                    "bytes": int(r["bytes"]), "latency_ms": lat,
                })
        rows.sort(key=lambda row: -row["bytes"])
        return rows

    # --- export -----------------------------------------------------------

    def summary(self) -> dict:
        """Seed-compatible flat dict: phase wall-clocks, counters, gauges,
        the derived throughput, and ``<hist>/{p50,p95,max,count}``
        flattened histogram entries."""
        with self._lock:
            out = {f"time/{k}_s": round(v, 4) for k, v in self.phases.items()}
            out.update(self.counters)
            out.update(self.gauges)
            merged = {**self.counters, **self.gauges}
            hists = list(self.histograms.items())
            phases = dict(self.phases)
        for name, h in hists:
            s = h.summary()
            for stat in ("p50", "p95", "max", "count"):
                out[f"{name}/{stat}"] = s[stat]
        total_records = merged.get("records_in")
        map_reduce_s = sum(
            phases.get(p, 0.0) for p in ("map+reduce", "finalize")
        )
        if total_records and map_reduce_s > 0:
            out["records_per_sec"] = round(total_records / map_reduce_s, 1)
        return out

    def to_dict(self) -> dict:
        """Structured export (the ``--metrics-out`` document): phases,
        counters, gauges, full histogram summaries, and the comms table,
        unflattened."""
        with self._lock:
            out = {
                "phases_s": {k: round(v, 6) for k, v in self.phases.items()},
                "counters": dict(self.counters),
                "gauges": dict(self.gauges),
                "histograms": {k: h.summary()
                               for k, h in self.histograms.items()},
            }
        comms = self.comms_table()
        if comms:
            out["comms"] = comms
        return out


def sample_collective_wall(holder, attr: str, t0: float,
                           target) -> float | None:
    """Shared sampling rule for async collective sites: on the 1st and
    then every ``SAMPLE_EVERY``-th invocation (counted per ``holder``
    via ``attr`` — the SAME cadence the xprof device-compute sampler
    uses, so the forced sync is one the observatory was paying anyway),
    force ``target`` with ``jax.block_until_ready`` and return the wall
    since ``t0`` in ms — the containing dispatch's completion wall, the
    honest latency figure available for a collective that lowers into a
    larger program.  Returns None on unsampled invocations."""
    n = getattr(holder, attr, 0) + 1
    setattr(holder, attr, n)
    from map_oxidize_tpu.obs.compile import SAMPLE_EVERY

    if n != 1 and n % SAMPLE_EVERY != 0:
        return None
    try:
        import jax

        jax.block_until_ready(target)
        return (time.perf_counter() - t0) * 1e3
    except Exception:
        return None


def _shape_str(shape) -> str:
    """Stable string key for a comms row's buffer shape.  Callers may pass
    a tuple, an already-formatted string (shape plus a dtype tag), or
    None (shapeless host collectives)."""
    if shape is None:
        return "-"
    if isinstance(shape, str):
        return shape
    try:
        return "x".join(str(int(d)) for d in shape)
    except TypeError:
        return str(shape)


def format_bytes(n) -> str:
    """Human-readable byte count (the shared table-rendering helper —
    `obs top`, `obs calib`)."""
    if not isinstance(n, (int, float)):
        return "-"
    for scale, suffix in ((1 << 40, "TB"), (1 << 30, "GB"),
                          (1 << 20, "MB"), (1 << 10, "KB")):
        if n >= scale:
            return f"{n / scale:.2f}{suffix}"
    return f"{n:.0f}B"


# --- memory watermarks ----------------------------------------------------


def sample_host_memory(registry: MetricsRegistry) -> None:
    """Record host RSS watermarks: current ``VmRSS`` and the kernel's own
    high-water ``VmHWM`` from ``/proc/self/status`` (Linux), falling back
    to ``resource.getrusage`` peak RSS elsewhere.  Cheap (~µs), called at
    phase boundaries — where residency peaks live (finalize fetches, sort
    buffers, write staging)."""
    rss = hwm = None
    try:
        with open("/proc/self/status") as f:
            for line in f:
                if line.startswith("VmRSS:"):
                    rss = int(line.split()[1]) * 1024
                elif line.startswith("VmHWM:"):
                    hwm = int(line.split()[1]) * 1024
    except OSError:
        pass
    if hwm is None:
        try:
            import resource

            hwm = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024
        except Exception:
            return
    if rss is not None:
        registry.gauge_max("mem/host_rss_bytes", rss)
    registry.gauge_max("mem/host_rss_peak_bytes", hwm)


def sample_device_memory(registry: MetricsRegistry) -> None:
    """Record HBM watermarks from ``device.memory_stats()`` for every
    device jax has already initialized.  Deliberately a no-op when jax was
    never imported by the job (pure-host paths must not pay backend
    init), and tolerant of backends that expose no stats (CPU)."""
    import sys

    jax = sys.modules.get("jax")
    if jax is None:
        return
    try:
        devices = jax.devices()
    except Exception:
        return
    for d in devices:
        try:
            stats = d.memory_stats()
        except Exception:
            stats = None
        if not stats:
            continue
        in_use = stats.get("bytes_in_use")
        peak = stats.get("peak_bytes_in_use")
        if in_use is not None:
            registry.gauge_max(f"mem/device{d.id}_hbm_bytes", int(in_use))
        if peak is not None:
            registry.gauge_max(f"mem/device{d.id}_hbm_peak_bytes",
                               int(peak))

"""Unified observability: spans, metrics, and a progress heartbeat.

The reference's entire observability surface is a ``println!`` of the
top-10 (``/root/reference/src/main.rs:188-191``); the seed's was a flat
61-line phase-timer dict.  This package is the instrumentation discipline
the ROADMAP's scale targets require (the same discipline Exoshuffle,
arXiv:2203.05072, credits for making shuffle regressions debuggable):

* :class:`~map_oxidize_tpu.obs.trace.Tracer` — nested, thread-safe spans
  with attributes (rows, bytes, device, spill generation), exportable as
  Chrome trace-event JSON (``chrome://tracing`` / Perfetto) or JSONL.
* :class:`~map_oxidize_tpu.obs.metrics.MetricsRegistry` — counters,
  gauges, and lightweight histograms (p50/p95/max) behind the seed
  ``Metrics`` surface (``phase``/``count``/``set``/``summary``), so every
  existing consumer keeps working.
* :class:`~map_oxidize_tpu.obs.heartbeat.Heartbeat` — opt-in periodic
  progress lines (rows/sec, percent done, ETA, phase) for long streamed
  jobs.

:class:`Obs` bundles the three per job and owns the config wiring
(``--metrics-out`` / ``--trace-out`` / ``--progress``).  One ``Obs`` is
created per job run and *injected* into engines and checkpoint stores —
replacing the ad-hoc per-driver ``Metrics()`` instantiations — so every
layer (driver, engine, collect, shuffle, spill, checkpoint) records into
one coherent event model.

The job-level legs on top of the per-process bundle:

* :mod:`~map_oxidize_tpu.obs.merge` — multi-process trace/metrics
  shards, the merged cross-process Chrome trace (pid = process), and
  the straggler/skew report;
* :mod:`~map_oxidize_tpu.obs.ledger` — the append-only run ledger
  (``--ledger-dir``) with regression diffing (``obs diff``,
  ``bench.py --gate``) behind a version + config-hash identity check;
* :mod:`~map_oxidize_tpu.obs.flight` — the failure flight recorder
  (``--crash-dir``): aborts dump config/metrics/open-span-closed trace
  before propagating, and ``Obs.recording`` is the crash-safe envelope
  every driver wraps its body in.

The live telemetry plane on top of all of it (ISSUE-6):

* :mod:`~map_oxidize_tpu.obs.timeseries` — the ring-buffer time-series
  recorder (``--obs-sample-interval``): bounded timestamped series of
  every counter/gauge/quantile, exported as the metrics document's
  ``series`` section;
* :mod:`~map_oxidize_tpu.obs.serve` — the per-process HTTP server
  (``--obs-port``): ``/metrics`` (Prometheus), ``/status`` (live phase/
  progress/compile/MFU/comms; skew-aware aggregate on process 0),
  ``/series`` — shut down by ``finish`` AND the flight recorder;
* the **comms observatory**: every collective site records payload
  bytes + sampled latency (``MetricsRegistry.comm``) into per-
  (collective, program, shape) tables the ledger gate checks;
* :mod:`~map_oxidize_tpu.obs.context` — per-job routing so concurrent
  jobs in one process keep disjoint obs state.

The watcher on top of the live plane (ISSUE-9):

* :mod:`~map_oxidize_tpu.obs.slo` — declarative SLO rules evaluated
  continuously against the series ring (``--slo-rules``): firing/
  resolved state machines, ``[alert]`` heartbeat lines, the ``/alerts``
  endpoint, incident bundles, and ``alerts/*`` gate counters;
* :mod:`~map_oxidize_tpu.obs.trend` — cross-run regression forensics
  over the ledger history and BENCH rounds (``obs trend``): per-series
  trajectories, step-change detection, and the ranked movers report
  that attributes a gate failure to the counters that moved.

The layer above any one process (ISSUE-13):

* :mod:`~map_oxidize_tpu.obs.fleet` — the fleet observatory
  (``obs fleet``): a collector polling N obs endpoints (explicit
  targets, port files, serve spools, and the well-known port-record
  spool every serving process publishes into), merging them into one
  fleet model with staleness tracking, per-target labeled ``/metrics``
  + fleet aggregates (the multi-server load index), cross-target
  incident correlation at ``/alerts``, fleet-scope SLO rules through
  the same ``SloEvaluator``, and a bounded on-disk series archive that
  ``obs trend/top/where --archive`` read after every producer process
  has exited.

See ``docs/OBSERVABILITY.md`` for the event model and flag reference.
"""

from __future__ import annotations

import contextlib
import json
import os
import threading
from dataclasses import dataclass, field

from map_oxidize_tpu.obs.heartbeat import Heartbeat
from map_oxidize_tpu.obs.metrics import (
    Histogram,
    MetricsRegistry,
    sample_device_memory,
    sample_host_memory,
)
from map_oxidize_tpu.obs.trace import NULL_SPAN, Span, Tracer, span_of

__all__ = [
    "Heartbeat",
    "Histogram",
    "JobCancelled",
    "MetricsRegistry",
    "NULL_SPAN",
    "Obs",
    "Span",
    "Tracer",
    "sample_device_memory",
    "sample_host_memory",
    "span_of",
]


class JobCancelled(RuntimeError):
    """Cooperative cancellation: a client cancel or an expired deadline.

    Raised by :meth:`Obs.poll_cancel` from inside the job body — i.e.
    inside ``Obs.recording`` — so the abort takes the flight-recorder
    path like any other: open spans close, partial metrics/trace flush,
    and the crash bundle lands before the exception reaches the caller
    (the resident server's scheduler, which maps it to the job's
    ``cancelled`` state instead of ``failed``)."""


@dataclass
class Obs:
    """Per-job observability bundle: one registry, one tracer, and an
    optional heartbeat, threaded through driver -> engine -> spill layers.

    Always constructed (metrics were always-on in the seed too).  The
    tracer's spans always feed the registry (``span/<name>_s`` and the
    phase clocks, :meth:`MetricsRegistry.record_span`); it keeps the
    Chrome event list only when the job asked for a trace.
    """

    registry: MetricsRegistry
    tracer: Tracer
    heartbeat: Heartbeat | None = None
    #: this process's slot and the job's process count (multi-process
    #: runs; 0/1 for the single-controller drivers)
    process: int = 0
    n_processes: int = 1
    #: live device sampler (HBM watermarks + stall detector), when the
    #: config asked for either; stopped by finish/flight
    sampler: "object | None" = None
    #: compile-ledger snapshot taken at job start — finish deltas the
    #: process-global ledger against it for per-job xprof numbers
    xprof_base: "dict | None" = None
    #: live telemetry plane (``--obs-port`` / ``--obs-sample-interval``):
    #: the HTTP status server and the ring-buffer time-series recorder —
    #: both stopped by finish AND the flight recorder
    server: "object | None" = None
    series: "object | None" = None
    #: SLO plane (obs/slo.py): the alert evaluator watching the series
    #: ring — runs whenever the recorder runs, stopped with the live
    #: plane (its final tick sees the recorder's final sample)
    alerts: "object | None" = None
    #: the phase currently open (obs.phase) and the workload under
    #: recording — what /status reports while the job runs
    current_phase: "str | None" = None
    workload: "str | None" = None
    #: cooperative cancellation (the resident job service's cancel and
    #: deadline paths): set via :meth:`request_cancel` from ANY thread,
    #: observed at phase boundaries and per-block feeds by
    #: :meth:`poll_cancel`, which raises :class:`JobCancelled` inside the
    #: job body so the flight recorder runs
    cancel_event: threading.Event = field(default_factory=threading.Event)
    cancel_reason: "str | None" = None
    #: calibration store hookup (obs/calib.py): ``calib`` accumulates
    #: THIS run's measurements (seeded empty; merged into the store
    #: file at finish), ``calib_prior`` is the loaded cross-run history
    #: consumers read (collective chooser, auto-B).  Both None without
    #: ``--calib-dir`` — or when the store on disk refused to load
    calib: "object | None" = None
    calib_prior: "object | None" = None
    #: the job plan (runtime/planner.py + obs/plan.py): solved in
    #: ``recording`` before the body runs (knob choices + provenance +
    #: predicted wall), scored against the measured attribution in
    #: ``finish`` (``plan/model_error_pct``).  None with ``--plan off``
    #: or outside a workload body (the resident server's own bundle)
    plan: "dict | None" = None
    #: data-plane observatory (obs/dataplane.py): the per-partition
    #: row-conservation/skew audit the engines feed, created lazily by
    #: the driver through :meth:`ensure_dataplane` (the partition count
    #: is an engine fact the config alone doesn't know); None until
    #: then, and always None when ``config.data_audit`` is off
    dataplane: "object | None" = None
    dataplane_enabled: bool = True
    #: the ``job/setup`` span: opened by :meth:`from_config`, closed by
    #: the first phase (or ``finish``) — engine, plan and audit bring-up
    _setup_span: "Span | None" = None

    def __post_init__(self):
        if self.tracer.sink is None:
            self.tracer.sink = self.registry.record_span

    @classmethod
    def from_config(cls, config, process: int = 0,
                    n_processes: int = 1) -> "Obs":
        """Build the bundle a job's config asks for.  ``trace_out='-'``
        collects the trace for ``result.trace`` without writing a file.

        Multi-process jobs pass their slot: heartbeat lines are prefixed
        with the process id and emitted from process 0 only (every
        process advances in lockstep, so P copies of the same line are
        noise; ``MOXT_PROGRESS_ALL_PROCS=1`` un-silences the rest for
        per-process debugging)."""
        tracer = Tracer(enabled=bool(config.trace_out))
        obs_port = getattr(config, "obs_port", -1)
        sample_s = getattr(config, "obs_sample_s", 0.0)
        live = obs_port >= 0 or sample_s > 0
        if live and sample_s <= 0:
            sample_s = 1.0  # serving implies sampling: /series must work
        hb = None
        if getattr(config, "progress", False) or live:
            total = None
            try:
                total = os.path.getsize(config.input_path)
            except OSError:
                pass
            emit = None
            # silent heartbeat: the live plane needs the row/phase/ETA
            # accumulation for /status even when --progress printing is
            # off — emit becomes a no-op, the tracking is identical
            silent = not getattr(config, "progress", False)
            if n_processes > 1 and not silent:
                if process != 0 and not os.environ.get(
                        "MOXT_PROGRESS_ALL_PROCS"):
                    silent = True  # lockstep: P copies of a line are noise
                else:
                    from map_oxidize_tpu.utils.logging import get_logger

                    plog = get_logger(__name__)
                    emit = (lambda line, _p=process:
                            plog.info("[proc %d] %s", _p, line))
            if silent and not live:
                pass  # progress off, no live plane: no heartbeat at all
            else:
                if silent:
                    emit = lambda line: None
                hb = Heartbeat(total_bytes=total,
                               interval_s=config.progress_interval_s,
                               emit=emit)
                hb.silent = silent
        obs = cls(registry=MetricsRegistry(), tracer=tracer, heartbeat=hb,
                  process=process, n_processes=n_processes,
                  dataplane_enabled=bool(
                      getattr(config, "data_audit", True)))
        obs._setup_span = tracer.span("job/setup").__enter__()
        # the XLA program observatory is always-on: compile counts, costs
        # and dispatch gaps accrue in the process-global ledger; the job
        # deltas against this baseline at finish (obs/compile.py)
        from map_oxidize_tpu.obs import compile as _compile

        obs.xprof_base = _compile.LEDGER.activate(obs)
        hbm_s = getattr(config, "hbm_sample_s", 0.0)
        stall = getattr(config, "stall_warn_factor", 0.0)
        if live and hbm_s <= 0:
            # the live plane implies the HBM sampler: /status and the
            # time series carry hbm/live_bytes at the sample cadence
            hbm_s = sample_s
        if hbm_s > 0 or stall > 0:
            from map_oxidize_tpu.obs.xprof import DeviceSampler

            obs.sampler = DeviceSampler(obs, interval_s=hbm_s,
                                        stall_factor=stall)
            obs.sampler.start()
        if sample_s > 0:
            from map_oxidize_tpu.obs.timeseries import (
                DEFAULT_CAPACITY,
                TimeSeriesRecorder,
            )

            # MOXT_SERIES_CAPACITY: test hook for ring-wraparound
            # coverage — a tiny ring wraps in seconds instead of a
            # 17-minute soak (tests/test_slo.py)
            try:
                cap = int(os.environ.get("MOXT_SERIES_CAPACITY", "")
                          or DEFAULT_CAPACITY)
            except ValueError:
                cap = DEFAULT_CAPACITY
            obs.series = TimeSeriesRecorder(obs.registry,
                                            interval_s=sample_s,
                                            capacity=cap,
                                            heartbeat=obs.heartbeat,
                                            obs=obs)
            obs.series.start()
            # the SLO plane rides the series ring: default rules plus
            # --slo-rules, evaluated at the sampling cadence; incident
            # bundles land under --incident-dir (default: --crash-dir)
            from map_oxidize_tpu.obs.slo import SloEvaluator, load_rules

            obs.alerts = SloEvaluator(
                obs, load_rules(getattr(config, "slo_rules", None)),
                config=config, interval_s=sample_s,
                incident_dir=(getattr(config, "incident_dir", None)
                              or getattr(config, "crash_dir", None)))
            obs.alerts.start()
        if obs_port >= 0:
            from map_oxidize_tpu.obs.serve import (
                ObsServer,
                serve_port_for_process,
            )

            obs.server = ObsServer(
                obs, config, serve_port_for_process(obs_port, process))
            obs.server.start()
        calib_dir = getattr(config, "calib_dir", None)
        if calib_dir:
            from map_oxidize_tpu.obs import calib as _calib

            path = os.path.join(calib_dir, _calib.CALIB_FILE)
            try:
                # prior history loads for consumers (collective chooser,
                # auto-B warm figures); the RUN accumulator is a fresh
                # empty store so the finish-time merge never double-
                # counts the history already on disk
                obs.calib_prior = _calib.CalibStore.load(path)
                obs.calib = _calib.CalibStore(path=path)
                # cold-store visibility: 0 runs = a restarted server
                # with an empty store (the fleet-calib-cold SLO rule
                # and the fleet rollup read this)
                obs.registry.set(
                    "calib/store_runs",
                    obs.calib_prior.doc.get("runs", 0))
            except _calib.CalibMismatch as e:
                # refusal is the contract: stale/torn evidence must not
                # merge — the run proceeds uncalibrated, loudly
                obs.registry.set("calib/load_refused", 1)
                from map_oxidize_tpu.utils.logging import get_logger

                get_logger(__name__).warning(
                    "calibration store refused to load: %s", e)
        return obs

    def ensure_dataplane(self, n_partitions: int, conserves: bool = True):
        """Create (once) and return the data-plane audit
        (:class:`~map_oxidize_tpu.obs.dataplane.DataPlaneAudit`), or
        None when ``config.data_audit`` disabled it.  Drivers call this
        as soon as they know the effective partition count; engines and
        transports then feed ``obs.dataplane`` directly."""
        if not self.dataplane_enabled:
            return None
        if self.dataplane is None:
            from map_oxidize_tpu.obs.dataplane import DataPlaneAudit

            self.dataplane = DataPlaneAudit(n_partitions,
                                            conserves=conserves)
        return self.dataplane

    def finish_dataplane(self) -> "dict | None":
        """Publish the ``data/*`` gauges and return the structured audit
        section (``doc["data"]``) — called by ``finish`` and its
        distributed twin BEFORE the registry summary is taken, so the
        ledger entry carries the gauges.  None when no audit ran."""
        if self.dataplane is None:
            return None
        self.dataplane.publish(self.registry)
        return self.dataplane.doc()

    def knob(self, name: str, fallback):
        """The planner-effective value of a tunable knob: the plan's
        chosen value when a plan exists, else the caller's config value.
        Drivers consult this instead of the raw config so a curve-driven
        choice applies WITHOUT mutating the config (the ledger's
        config-hash identity must not depend on what the planner
        chose)."""
        p = self.plan
        if p:
            row = (p.get("knobs") or {}).get(name)
            if row is not None and row.get("value") is not None:
                return row["value"]
        return fallback

    def request_cancel(self, reason: str = "cancelled") -> None:
        """Ask the job to stop at its next cancellation point (phase
        boundary or per-block feed).  Thread-safe; the first reason
        wins.  A job that never reaches another cancellation point (a
        wedged collective) is the stall detector's department — this is
        the cooperative path."""
        if not self.cancel_event.is_set():
            self.cancel_reason = reason
            self.cancel_event.set()

    def poll_cancel(self) -> None:
        """Raise :class:`JobCancelled` if a cancel was requested.  Called
        at every phase start and per-block feed; one ``Event.is_set``
        check on the not-cancelled path."""
        if self.cancel_event.is_set():
            raise JobCancelled(self.cancel_reason or "cancelled")

    def end_setup(self) -> None:
        """Close the ``job/setup`` span (Obs creation to the first phase:
        config, engine, backend and audit bring-up — the attribution
        ledger's ``setup`` bucket source).  Idempotent."""
        sp, self._setup_span = self._setup_span, None
        if sp is not None:
            sp.__exit__(None, None, None)

    @contextlib.contextmanager
    def phase(self, name: str, **attrs):
        """One job phase: a top-level span (whose sink keeps the
        ``time/<name>_s`` phase clock), the heartbeat's current phase
        label, and a host-RSS watermark sample on exit (phase boundaries
        are where residency peaks: finalize fetches, sort buffers, write
        staging).  Also a cancellation point (:meth:`poll_cancel`)."""
        self.poll_cancel()
        self.end_setup()
        if self.heartbeat is not None:
            self.heartbeat.set_phase(name)
        prev, self.current_phase = self.current_phase, name
        with self.tracer.span(f"phase/{name}", **attrs):
            try:
                yield
            finally:
                self.current_phase = prev
                sample_host_memory(self.registry)

    def feed_span(self, **attrs) -> "Span":
        """Span for one mapped block's engine feed (the per-block latency
        site every driver instruments) — and the job's fine-grained
        cancellation point: a cancel/deadline lands between blocks, never
        mid-feed."""
        self.poll_cancel()
        return self.tracer.span("engine/feed_block", **attrs)

    def stamp(self, config, workload: str | None = None) -> dict:
        """Provenance stamp carried by every exported document (metrics,
        trace, shard, ledger entry, crash bundle): the package version
        plus the identity config hash — what ``obs diff``/``obs merge``
        check before comparing or combining — and the process slot."""
        from map_oxidize_tpu import __version__
        from map_oxidize_tpu.obs.ledger import config_hash

        return {
            "version": __version__,
            "config_hash": config_hash(config),
            "workload": workload,
            "process": self.process,
            "n_processes": self.n_processes,
            "wall_start_unix_s": round(self.tracer.wall_start, 6),
        }

    def stop_live(self) -> None:
        """Quiesce the live telemetry plane: stop the HTTP server (no
        scrape may observe a half-finished export) and the time-series
        recorder (which takes its final sample).  Idempotent; called by
        ``finish`` AND the flight recorder."""
        if self.server is not None:
            self.server.stop()
        if self.series is not None:
            self.series.stop()
        if self.alerts is not None:
            # after the recorder's final sample, so a condition that
            # cleared at the very end still resolves in the timeline
            self.alerts.stop()

    def finish_xprof(self) -> dict | None:
        """Close the job's XLA observatory window: stop the sampler,
        release the compile-ledger hookup, and fold the per-job delta
        (compile counts, per-program MFU, bound classification) into the
        registry as flat ``compile/*`` / ``xprof/*`` gauges — the fields
        the run ledger and ``obs diff --gate`` compare.  Returns the
        structured report for the metrics document (None on a second
        call or when the observatory never opened)."""
        from map_oxidize_tpu.obs import compile as _compile
        from map_oxidize_tpu.obs import xprof

        if self.sampler is not None:
            self.sampler.stop()
            self.sampler = None
        local = _compile.LEDGER.deactivate(self)
        base, self.xprof_base = self.xprof_base, None
        if base is None:
            return None
        report = xprof.job_report(_compile.LEDGER.job_delta(base, local))
        for k, v in xprof.flatten_report(report).items():
            self.registry.set(k, v)
        return report

    def _merge_calibration(self, xprof_report: dict | None,
                           workload: str | None = None,
                           corpus_bytes: float = 0.0,
                           attrib_doc: dict | None = None) -> None:
        """Fold this run's comms table + xprof program rows — plus the
        per-workload wall-attribution curve row the planner's wall
        prediction reads (obs/calib.py ``workloads`` section) — into
        the persistent calibration store and merge it atomically into
        the store file.  A refusal (schema/identity mismatch on disk)
        records ``calib/merge_refused`` and moves on — the job's own
        result is never hostage to the store."""
        if self.calib is None:
            return
        from map_oxidize_tpu.obs import calib as _calib
        from map_oxidize_tpu.utils.logging import get_logger

        try:
            ident = _calib.run_identity(self.n_processes)
            touched = self.calib.accumulate_run(
                ident, self.registry.comms_table(), xprof_report,
                source="job")
            if workload and workload != "serve":
                touched += self.calib.accumulate_workload(
                    ident, workload, corpus_bytes, attrib_doc)
            if touched:
                self.calib.save_merged()
                self.registry.set("calib/rows_merged", touched)
                self.registry.set(
                    "calib/runs", self.calib.doc.get("runs", 0))
        except _calib.CalibMismatch as e:
            self.registry.set("calib/merge_refused", 1)
            get_logger(__name__).warning(
                "calibration store refused the merge: %s", e)
        except Exception as e:  # pragma: no cover - the store is
            # evidence, never a reason to fail a finished job
            get_logger(__name__).warning("calibration merge failed: %s", e)

    def _conclude(self, config, workload: str | None):
        """The end-of-job analysis ``finish`` runs under its ``job/finish``
        span: stop the live plane, close the xprof window, attribute the
        wall, score the plan, merge calibration, publish the audit, take
        the final memory watermarks.  Returns ``(xprof_report,
        attrib_doc, critpath_doc, data_doc)``."""
        self.stop_live()
        xprof_report = self.finish_xprof()
        # the end-of-job wall attribution: buckets + unattributed
        # remainder as attrib/* gauges (ledger/gate/BENCH_DETAIL) and
        # the structured section the metrics document carries
        import time as _time

        from map_oxidize_tpu.obs import attrib as _attrib

        attrib_doc = _attrib.finalize(
            self, xprof_report,
            max(_time.time() - self.tracer.wall_start, 1e-9))
        # the causal layer's single-process form: the critical path
        # degenerates to the attribution timeline, but the SAME headline
        # gauges (critpath/bound_frac, path coverage, bound_by) land in
        # the summary -> ledger entry, so trend/gate watch one axis
        # across single- and multi-process runs.  The resident server's
        # own bundle idles between jobs — no job wall to decompose.
        critpath_doc = None
        if workload != "serve":
            from map_oxidize_tpu.obs import critpath as _critpath

            try:
                critpath_doc = _critpath.degenerate_from_attrib(
                    attrib_doc, process=self.process)
                _critpath.publish(self.registry, critpath_doc)
            except ValueError:
                pass
        # score the plan against the measured attribution (predicted
        # vs actual wall per bucket; plan/model_error_pct when the plan
        # actually predicted) BEFORE the summary below, so the ledger
        # entry and the gate carry the error gauge
        if self.plan is not None:
            from map_oxidize_tpu.obs import plan as _plan

            try:
                _plan.finalize(self, self.plan, attrib_doc)
            except Exception:  # pragma: no cover - scoring is evidence,
                pass           # never a reason to fail a finished job
        corpus_bytes = 0.0
        try:
            corpus_bytes = float(os.path.getsize(config.input_path))
        except (OSError, TypeError, AttributeError):
            pass
        self._merge_calibration(xprof_report, workload=workload,
                                corpus_bytes=corpus_bytes,
                                attrib_doc=attrib_doc)
        # the data-plane audit lands before the summary below, so the
        # ledger entry (and obs diff --gate) carries the data/* gauges
        data_doc = self.finish_dataplane()
        sample_host_memory(self.registry)
        sample_device_memory(self.registry)
        if self.heartbeat is not None:
            self.heartbeat.final_beat()
        return xprof_report, attrib_doc, critpath_doc, data_doc

    def finish(self, config, workload: str | None = None
               ) -> tuple[dict, list | None]:
        """End-of-job hook: the analysis of :meth:`_conclude` under the
        ``job/finish`` span, then flag-driven file exports (version/
        config-hash stamped), the optional ledger append, and the
        ``(summary, trace_events)`` pair the result carries.
        ``trace_events`` is None when tracing was off."""
        self.end_setup()
        with self.tracer.span("job/finish"):
            xprof_report, attrib_doc, critpath_doc, data_doc = (
                self._conclude(config, workload))
        meta = self.stamp(config, workload)
        if config.metrics_out:
            doc = dict(self.registry.to_dict(), meta=meta)
            doc["attrib"] = attrib_doc
            if self.plan is not None:
                doc["plan"] = self.plan
            if critpath_doc is not None:
                doc["critpath"] = critpath_doc
            if data_doc is not None:
                doc["data"] = data_doc
            if xprof_report is not None:
                doc["xprof"] = xprof_report
            if self.series is not None:
                doc["series"] = self.series.export()
            if self.alerts is not None:
                doc["alerts"] = self.alerts.export()
            write_json_atomic(config.metrics_out, doc)
        trace = self.tracer.chrome_trace() if self.tracer.enabled else None
        if trace is not None:
            trace.insert(0, {"name": "moxt_meta", "ph": "M",
                             "pid": self.tracer._pid, "tid": 0,
                             "args": meta})
            if config.trace_out != "-":
                # dump the list just built — rebuilding it via
                # write_chrome would pay the tid-compaction pass twice
                write_json_atomic(config.trace_out, trace, indent=None)
        summary = self.registry.summary()
        if getattr(config, "ledger_dir", None):
            from map_oxidize_tpu.obs import ledger

            extra: dict = {}
            if self.plan is not None:
                # the full plan doc rides the entry (knobs + provenance
                # + predicted wall per bucket) — `obs plan` renders it
                # straight from ledger history, and the flat plan/*
                # gauges are already in the summary the gate compares
                extra["plan"] = self.plan
            comms = self.registry.comms_table()
            if comms:
                extra["comms"] = comms
            if data_doc is not None:
                from map_oxidize_tpu.obs.dataplane import ledger_section

                extra["data"] = ledger_section(data_doc)
            if self.alerts is not None and (self.alerts.fired_total
                                            or self.alerts.resolved_total):
                # the alert timeline rides the entry (the flat
                # alerts/fired counter is already in the summary the
                # gate compares)
                extra["alerts"] = self.alerts.timeline_doc()
            ledger.append(config.ledger_dir, ledger.build_entry(
                config, workload or "?", summary,
                n_processes=self.n_processes,
                extra=extra or None))
        return summary, trace

    @contextlib.contextmanager
    def recording(self, config, workload: str | None = None):
        """Crash-safe envelope for a job body: on ANY exception the
        flight recorder closes open spans, flushes the partial metrics/
        trace to their configured paths, and dumps a post-mortem bundle
        under ``config.crash_dir`` — then the exception propagates
        unchanged.  Zero cost on the success path.

        Also binds this bundle as the context's current job
        (:mod:`map_oxidize_tpu.obs.context`), so per-dispatch
        observations from concurrent jobs in one process route to their
        own registries."""
        from map_oxidize_tpu.obs.context import use_obs

        self.workload = workload
        if (self.plan is None and workload and workload != "serve"
                and getattr(config, "plan", "auto") != "off"):
            # the job plan: solve the knobs + predict the wall BEFORE
            # the body runs, from the calibration store's curves; the
            # plan/* gauges land now so /status and the time series
            # carry the plan while the job runs (obs/plan.py scores it
            # at finish).  Planning is evidence — never a reason to
            # fail the job it describes.
            from map_oxidize_tpu.obs import plan as _plan
            from map_oxidize_tpu.runtime import planner as _planner

            try:
                self.plan = _planner.build_plan(
                    config, workload, calib_prior=self.calib_prior,
                    n_processes=self.n_processes)
                _plan.publish(self.registry, self.plan)
            except Exception as e:
                from map_oxidize_tpu.utils.logging import get_logger

                get_logger(__name__).warning("job planning failed: %s", e)
        try:
            with use_obs(self):
                yield self
        except BaseException as exc:
            from map_oxidize_tpu.obs import flight

            flight.record_failure(self, config, exc, workload=workload)
            raise


def write_json_atomic(path: str, payload, indent: int | None = 1) -> None:
    """Write ``payload`` as JSON via temp-file + rename (same atomicity
    contract as every other artifact writer in the repo).  ``indent=None``
    for bulk documents (trace event lists) where compactness wins."""
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "w") as f:
        json.dump(payload, f, indent=indent, default=_json_default)
    os.replace(tmp, path)


def _json_default(o):
    """Numpy scalars leak into counters from engine code; make them JSON."""
    item = getattr(o, "item", None)
    if item is not None:
        return item()
    raise TypeError(f"not JSON serializable: {type(o)!r}")

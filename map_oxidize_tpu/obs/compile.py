"""Compile ledger: observe every jitted program the framework owns.

Round 5's decomposition found that *dispatch cost* — not link bandwidth —
is the binding constraint on streamed k-means, and the finding required a
hand-run characterization script because nothing in the obs stack could
see compiles, recompiles, per-program cost, or per-dispatch overhead.
DrJAX (arXiv:2403.07128) makes the same point structurally: MapReduce-in-
JAX performance lives or dies on keeping the per-round program count and
recompile rate flat.  This module is the always-on accounting for it:

* :func:`observed_jit` wraps a jitted callable under a stable *program
  name*.  Each call is timed (the **dispatch gap**: host handoff ->
  async return — the per-launch floor) and compiles are detected via
  the jit cache size
  growing across the call.  A program compiling more than once gets a
  named **recompile cause** (new input shape / new dtype / new static
  config / retrace) derived by diffing the new signature against the
  seen set.
* At compile time the wrapper captures ``Lowered.cost_analysis()``
  (FLOPs, bytes accessed — no backend compile needed), which
  :mod:`map_oxidize_tpu.obs.xprof` later joins with per-dispatch timing
  into achieved FLOP/s / bytes/s and an MFU figure per program.
* Backend-compile wall time is attributed precisely through a
  ``jax.monitoring`` duration listener scoped by a thread-local
  current-program marker (falling back to the compiling call's wall).

The ledger is process-global (jit executable caches are process-global);
jobs see per-job numbers by snapshotting at ``Obs`` creation and
exporting the delta at finish (:meth:`CompileLedger.export_job`).
Overhead per observed dispatch is two ``perf_counter`` reads and a dict
probe; the sampled device-compute read (``block_until_ready`` every
``sample_every``-th dispatch per program) is the only sync added.
"""

from __future__ import annotations

import threading
import time

from map_oxidize_tpu.obs.trace import span_of
from map_oxidize_tpu.utils.logging import get_logger

_log = get_logger(__name__)

#: sample the post-return device-compute wait on the 1st and then every
#: N-th dispatch of each program (bounded sync cost on async pipelines)
SAMPLE_EVERY = 16


class ProgramStats:
    """Cumulative per-program record (keyed by program *name*, so fresh
    per-job jit closures of the same program aggregate)."""

    __slots__ = ("name", "compiles", "compile_ms", "backend_compile_ms",
                 "dispatches", "dispatch_ms", "sampled_ms", "samples",
                 "causes", "sigs", "flops", "bytes_accessed", "chunks")

    def __init__(self, name: str):
        self.name = name
        self.compiles = 0
        self.compile_ms = 0.0          # wall of the compiling calls
        self.backend_compile_ms = 0.0  # attributed XLA backend time
        self.dispatches = 0
        self.dispatch_ms = 0.0         # host handoff -> async return
        self.sampled_ms = 0.0          # sampled post-return ready waits
        self.samples = 0
        #: logical chunks retired by the NON-compiling dispatches (a
        #: scan-batched program retires B chunks per launch; matches the
        #: dispatch_ms population so per-chunk gap = dispatch_ms/chunks)
        self.chunks = 0
        self.causes: list[str] = []
        #: signature -> (flops, bytes) cost from Lowered.cost_analysis
        self.sigs: dict = {}
        # latest known per-dispatch cost (None = analysis unavailable)
        self.flops: float | None = None
        self.bytes_accessed: float | None = None

    def snapshot(self) -> tuple:
        return (self.compiles, self.compile_ms, self.backend_compile_ms,
                self.dispatches, self.dispatch_ms, self.sampled_ms,
                self.samples, len(self.causes), self.chunks)


class CompileLedger:
    """Process-global registry of observed programs plus the active job's
    :class:`~map_oxidize_tpu.obs.Obs` hookup (histograms + heartbeat
    warnings go to whichever job is currently recording)."""

    def __init__(self):
        self._lock = threading.Lock()
        self.programs: dict[str, ProgramStats] = {}
        self._active = None       # the latest-activated job's Obs (or None)
        self._active_base: dict = {}  # its activation snapshot
        #: ALL currently-recording jobs: id(obs) -> [obs, base snapshot,
        #: local overlay].  Dispatch observations route to the CONTEXT's
        #: job first (obs.context — set by Obs.recording), falling back
        #: to the latest-activated one, so two concurrent jobs in one
        #: process keep disjoint histograms, warnings, sample cadences,
        #: AND per-job compile/dispatch counts: the overlay accumulates
        #: only the activity routed to that job, where the global-minus-
        #: baseline delta would credit every concurrent job with the
        #: union.
        self._actives: dict[int, list] = {}
        self._tls = threading.local()
        self._listener_on = False

    # --- job lifecycle ----------------------------------------------------

    def activate(self, obs) -> dict:
        """Mark ``obs`` as a recording job; returns the baseline
        snapshot its finish will delta against."""
        with self._lock:
            self._active = obs
            self._active_base = {n: p.snapshot()
                                 for n, p in self.programs.items()}
            self._actives[id(obs)] = [obs, dict(self._active_base), {}]
            return dict(self._active_base)

    def deactivate(self, obs) -> "dict | None":
        """Close the job's window; returns its local overlay (the per-job
        activity record ``job_delta`` consumes)."""
        with self._lock:
            entry = self._actives.pop(id(obs), None)
            if self._active is obs:
                if self._actives:
                    # another job is still recording: it becomes the
                    # fallback for context-less dispatch sites
                    other = next(iter(self._actives.values()))
                    self._active, self._active_base = other[0], other[1]
                else:
                    self._active = None
                    self._active_base = {}
        return entry[2] if entry is not None else None

    def overlay(self, obs) -> "dict | None":
        """Copy of a still-active job's local overlay (the live /status
        table reads this without closing the window)."""
        with self._lock:
            entry = self._actives.get(id(obs))
            return ({n: dict(r, causes=list(r["causes"]))
                     for n, r in entry[2].items()}
                    if entry is not None else None)

    def _job(self) -> list:
        """The [obs, baseline, overlay] a dispatch observation belongs
        to: the context-bound job when one is recording, else the latest
        activated (single-job processes never notice the difference)."""
        from map_oxidize_tpu.obs.context import current_obs

        cur = current_obs()
        if cur is not None:
            entry = self._actives.get(id(cur))
            if entry is not None:
                return entry
        entry = self._actives.get(id(self._active))
        if entry is not None:
            return entry
        return [self._active, self._active_base, None]

    @staticmethod
    def _local_row(local: dict, name: str) -> dict:
        row = local.get(name)
        if row is None:
            row = local[name] = {
                "compiles": 0, "compile_ms": 0.0,
                "backend_compile_ms": 0.0, "dispatches": 0,
                "dispatch_ms": 0.0, "sampled_ms": 0.0, "samples": 0,
                "chunks": 0, "causes": []}
        return row

    # --- recording (called from ObservedJit) ------------------------------

    def _stats(self, name: str) -> ProgramStats:
        p = self.programs.get(name)
        if p is None:
            with self._lock:
                p = self.programs.setdefault(name, ProgramStats(name))
        return p

    def _ensure_listener(self) -> None:
        """Attribute XLA backend-compile durations to the program whose
        call triggered them (thread-local marker; registration is global
        and permanent, so it happens at most once per process)."""
        if self._listener_on:
            return
        with self._lock:
            if self._listener_on:
                return
            try:
                import jax.monitoring as mon

                def _on_duration(event: str, duration: float, **kw):
                    if not event.endswith("backend_compile_duration"):
                        return
                    cur = getattr(self._tls, "current", None)
                    if cur is not None:
                        cur.backend_compile_ms += duration * 1e3

                mon.register_event_duration_secs_listener(_on_duration)
                self._listener_on = True
            except Exception:  # monitoring API drift must not break jobs
                self._listener_on = True

    def record_compile(self, stats: ProgramStats, sig, cause: str,
                       wall_ms: float, cost,
                       backend_ms: float = 0.0) -> None:
        with self._lock:
            stats.compiles += 1
            stats.compile_ms += wall_ms
            if cause != "first":
                stats.causes.append(cause)
            if sig is not None:
                stats.sigs[sig] = cost
            if cost is not None:
                stats.flops, stats.bytes_accessed = cost
        obs, base, local = self._job()
        job_compiles = stats.compiles - base.get(stats.name, (0,))[0]
        if local is not None:
            with self._lock:
                row = self._local_row(local, stats.name)
                row["compiles"] += 1
                row["compile_ms"] += wall_ms
                row["backend_compile_ms"] += backend_ms
                if cause != "first":
                    row["causes"].append(cause)
                job_compiles = row["compiles"]
        # warn on the job's OWN recompiles only: a later job in the same
        # process legitimately compiles programs an earlier job already
        # ran (new static configs, new shapes) — the per-job delta the
        # gate reads handles those; the live warning is for a program
        # compiling twice within ONE job (a shape-set leak in flight)
        if job_compiles > 1 and obs is not None:
            line = (f"[xprof] recompile #{job_compiles} of {stats.name} "
                    f"this job: {cause} ({len(stats.sigs)} input-shape "
                    "sets)")
            hb = obs.heartbeat
            if hb is not None and not getattr(hb, "silent", False):
                hb._emit(line)
            else:
                # a silent tracking-only heartbeat (live plane without
                # --progress) must not swallow the warning
                _log.warning("%s", line)

    def record_dispatch(self, stats: ProgramStats, gap_ms: float,
                        ready_ms: float | None, compiled: bool,
                        chunks: int = 1, batched: bool = False) -> None:
        """A compiling call's wall is compile time, not dispatch gap — it
        is excluded from the gap histogram and the per-program dispatch
        wall so steady-state overhead and rate estimates stay clean.

        ``chunks`` is the number of REAL logical chunks this one dispatch
        retired (a scan-batched program covers up to B; a padded tail
        block fewer): it accumulates next to the dispatch wall, and
        dispatches of a ``batched`` program (one that declares its chunk
        count) additionally land a ``device/dispatch_gap_per_chunk_ms``
        observation (gap / chunks) so dispatch-overhead histograms stay
        comparable across B — including the tail dispatch whose single
        real chunk pays the whole launch gap."""
        with self._lock:
            stats.dispatches += 1
            if not compiled:
                stats.dispatch_ms += gap_ms
                stats.chunks += chunks
            if ready_ms is not None:
                stats.sampled_ms += ready_ms
                stats.samples += 1
        obs, _base, local = self._job()
        if local is not None:
            with self._lock:
                row = self._local_row(local, stats.name)
                row["dispatches"] += 1
                if not compiled:
                    row["dispatch_ms"] += gap_ms
                    row["chunks"] += chunks
                if ready_ms is not None:
                    row["sampled_ms"] += ready_ms
                    row["samples"] += 1
        if obs is not None:
            if not compiled:
                obs.registry.observe("device/dispatch_gap_ms", gap_ms)
                if batched:
                    obs.registry.observe("device/dispatch_gap_per_chunk_ms",
                                         gap_ms / chunks)
            if ready_ms is not None:
                obs.registry.observe("device/compute_ms", ready_ms)

    # --- export -----------------------------------------------------------

    def job_delta(self, baseline: dict, local: "dict | None" = None
                  ) -> dict:
        """Per-program activity for one job window (programs with zero
        compiles AND zero dispatches in the window are omitted).

        With ``local`` (the overlay ``deactivate``/``overlay`` return),
        counts come from the activity actually ROUTED to that job — the
        only correct accounting when jobs overlap in one process.
        Without it, the global-minus-``baseline`` delta is used (exact
        for the one-job-at-a-time case; pre-overlay callers keep their
        semantics).  Cost facts (FLOPs/bytes, shape sets) are global
        program properties either way."""
        out = {}
        with self._lock:
            items = list(self.programs.items())
        if local is not None:
            stats = dict(items)
            for name, row in local.items():
                if row["compiles"] <= 0 and row["dispatches"] <= 0:
                    continue
                p = stats.get(name)
                out[name] = {
                    "compiles": row["compiles"],
                    "compile_ms": round(row["compile_ms"], 3),
                    "backend_compile_ms": round(
                        row["backend_compile_ms"], 3),
                    "dispatches": row["dispatches"],
                    "dispatch_ms": round(row["dispatch_ms"], 3),
                    "sampled_device_ms": round(row["sampled_ms"], 3),
                    "device_samples": row["samples"],
                    "logical_chunks": row.get("chunks", 0),
                    "recompile_causes": list(row["causes"]),
                    "shape_sets": len(p.sigs) if p is not None else 0,
                    "flops_per_dispatch": p.flops if p else None,
                    "bytes_per_dispatch": p.bytes_accessed if p else None,
                }
            return out
        for name, p in items:
            b = baseline.get(name, (0, 0.0, 0.0, 0, 0.0, 0.0, 0, 0, 0))
            compiles = p.compiles - b[0]
            dispatches = p.dispatches - b[3]
            if compiles <= 0 and dispatches <= 0:
                continue
            out[name] = {
                "compiles": compiles,
                "compile_ms": round(p.compile_ms - b[1], 3),
                "backend_compile_ms": round(p.backend_compile_ms - b[2], 3),
                "dispatches": dispatches,
                "dispatch_ms": round(p.dispatch_ms - b[4], 3),
                "sampled_device_ms": round(p.sampled_ms - b[5], 3),
                "device_samples": p.samples - b[6],
                "logical_chunks": p.chunks - (b[8] if len(b) > 8 else 0),
                "recompile_causes": p.causes[b[7]:],
                "shape_sets": len(p.sigs),
                "flops_per_dispatch": p.flops,
                "bytes_per_dispatch": p.bytes_accessed,
            }
        return out


#: the process ledger every observed program records into
LEDGER = CompileLedger()


def _sig_of(args, kw):
    """Hashable signature of a call: (shape, dtype) per array leaf,
    ``repr`` for static/python leaves.  Weak-type and sharding changes
    are deliberately NOT keyed (the cache-size check still counts those
    compiles; the sig only names the cause)."""
    import jax

    leaves = jax.tree_util.tree_leaves((args, kw))
    sig = []
    for leaf in leaves:
        shape = getattr(leaf, "shape", None)
        dtype = getattr(leaf, "dtype", None)
        if shape is not None and dtype is not None:
            sig.append(("t", tuple(shape), str(dtype)))
        else:
            sig.append(("v", repr(leaf)))
    return tuple(sig)


def _classify(sig, seen: dict) -> str:
    """Name the recompile cause by diffing ``sig`` against seen ones."""
    shapes = tuple(s[1] for s in sig if s[0] == "t")
    dtypes = tuple(s[2] for s in sig if s[0] == "t")
    statics = tuple(s[1] for s in sig if s[0] == "v")
    for old in seen:
        o_shapes = tuple(s[1] for s in old if s[0] == "t")
        o_dtypes = tuple(s[2] for s in old if s[0] == "t")
        o_statics = tuple(s[1] for s in old if s[0] == "v")
        if shapes != o_shapes and dtypes == o_dtypes and statics == o_statics:
            return "new_input_shape"
        if shapes == o_shapes and dtypes != o_dtypes:
            return "new_dtype"
        if shapes == o_shapes and dtypes == o_dtypes and statics != o_statics:
            return "new_static_config"
    return "signature_change"


class ObservedJit:
    """A jitted callable under compile/dispatch observation.

    Transparent: ``.lower``/attributes pass through to the wrapped jit,
    calls made *inside* another trace (tracer arguments) bypass the
    bookkeeping entirely, and donation semantics are untouched (the
    signature and cost analysis are taken BEFORE the call, while donated
    buffers are still valid).
    """

    def __init__(self, name: str, fn, tag=None, ledger: CompileLedger = None,
                 sample_every: int = SAMPLE_EVERY, chunks_of=None):
        self._name = name
        self._fn = fn
        #: extra static identity folded into the signature (e.g. the
        #: stream step's first/last flags, which live in the closure)
        self._tag = tag
        self._ledger = ledger if ledger is not None else LEDGER
        self._sample_every = sample_every
        #: optional ``(args, kw) -> int``: how many LOGICAL chunks one
        #: dispatch of this program retires (a scan-batched program
        #: covers B per launch) — drives the per-logical-chunk
        #: dispatch-gap attribution; None = 1 chunk per dispatch
        self._chunks_of = chunks_of
        self._ledger._ensure_listener()

    def __getattr__(self, item):
        return getattr(self._fn, item)

    def _cache_n(self) -> int | None:
        size = getattr(self._fn, "_cache_size", None)
        try:
            return size() if callable(size) else None
        except Exception:
            return None

    def __call__(self, *args, **kw):
        import jax

        # reserved kwarg, consumed here (never forwarded to the jitted
        # fn): the REAL logical-chunk count of this dispatch, for call
        # sites whose padded block carries dead chunks the static
        # chunks_of shape cannot see (a tail block / padded drain) —
        # keeps per-chunk attribution consistent with the comms
        # accounting, which also excludes dead chunks
        explicit_chunks = kw.pop("observed_chunks", None)
        if any(isinstance(leaf, jax.core.Tracer)
               for leaf in jax.tree_util.tree_leaves((args, kw))):
            # called inside another program's trace: it inlines there and
            # is that outer program's cost, not a dispatch of this one
            return self._fn(*args, **kw)
        led = self._ledger
        stats = led._stats(self._name)
        sig = _sig_of(args, kw)
        if self._tag is not None:
            sig = sig + (("v", repr(self._tag)),)
        chunks = 1
        if explicit_chunks is not None:
            chunks = max(1, int(explicit_chunks))
        elif self._chunks_of is not None:
            # read the chunk count BEFORE the call: shapes survive
            # donation, but before-call is unconditionally safe
            try:
                chunks = max(1, int(self._chunks_of(*args, **kw)))
            except Exception:
                chunks = 1
        cost = None
        # the seen-set is ledger-level (keyed by program NAME): a fresh
        # per-job jit closure of the same program re-compiling the same
        # signature classifies as a retrace, not a new shape
        new_sig = sig not in stats.sigs
        if new_sig:
            # cost analysis from the lowering — BEFORE the call, so
            # donated operands are still live; no backend compile
            # happens.  The lowering itself is real wall (hundreds of
            # ms for a shard_map program) paid OUTSIDE the timed call
            # below — it feeds the attribution ledger's compile bucket
            # as attrib/lowering_ms, else the observatory's own
            # overhead would read as unattributed remainder
            t_lower = time.perf_counter()
            try:
                ca = self._fn.lower(*args, **kw).cost_analysis()
                if isinstance(ca, (list, tuple)):
                    ca = ca[0] if ca else {}
                if isinstance(ca, dict):
                    fl = float(ca.get("flops", -1.0))
                    by = float(ca.get("bytes accessed", -1.0))
                    cost = (fl if fl > 0 else None, by if by > 0 else None)
            except Exception:
                cost = None
            _lobs = led._job()[0]
            # phase-open guard mirrors pick_device's: a pre-phase
            # lowering is already covered by the setup gauge's window
            if _lobs is not None and getattr(_lobs, "current_phase",
                                             None):
                _lobs.registry.count(
                    "attrib/lowering_ms",
                    (time.perf_counter() - t_lower) * 1e3)
        before = self._cache_n()
        tls = led._tls
        prev_cur = getattr(tls, "current", None)
        tls.current = stats
        bc0 = stats.backend_compile_ms
        t0 = time.perf_counter()
        try:
            out = self._fn(*args, **kw)
        finally:
            tls.current = prev_cur
        gap_ms = (time.perf_counter() - t0) * 1e3
        after = self._cache_n()
        compiled = (after > before if (before is not None
                                       and after is not None) else new_sig)
        if compiled:
            cause = ("first" if not stats.sigs
                     else _classify(sig, stats.sigs)
                     if new_sig else "retrace_same_signature")
            led.record_compile(stats, sig if new_sig else None, cause,
                               gap_ms, cost,
                               backend_ms=stats.backend_compile_ms - bc0)
        elif new_sig:
            # the signature is new to the ledger but this jit already had
            # it cached (a pre-activation warm call): remember it so cost
            # joins and later cause classification stay complete
            with led._lock:
                stats.sigs.setdefault(sig, cost)
                if cost is not None and stats.flops is None:
                    stats.flops, stats.bytes_accessed = cost
        ready_ms = None
        # sample on the JOB-relative dispatch ordinal (the overlay's own
        # count, falling back to the delta from the activation
        # baseline), not the process-lifetime one: the first dispatch of
        # every job is always sampled, so the MFU join never silently
        # flips between the sampled-ready-wait and dispatch-wall
        # estimators across the runs a gate compares
        _obs, jbase, jlocal = led._job()
        if jlocal is not None:
            lrow = jlocal.get(self._name)
            n = (lrow["dispatches"] if lrow else 0) + 1
        else:
            base = jbase.get(self._name)
            n = stats.dispatches - (base[3] if base else 0) + 1
        if n <= 1 or n % self._sample_every == 0 or compiled:
            # the host blocked on the device: its own span in the job
            # (device_wait_s reads it), and the ledger's compute sample
            t1 = time.perf_counter()
            try:
                with span_of(_obs, "xprof/ready_wait", program=self._name):
                    jax.block_until_ready(out)
                ready_ms = (time.perf_counter() - t1) * 1e3
            except Exception:
                ready_ms = None
        led.record_dispatch(stats, gap_ms, ready_ms, compiled,
                            chunks=chunks,
                            batched=(explicit_chunks is not None
                                     or self._chunks_of is not None))
        return out


def job_overlay_delta(obs) -> dict:
    """Live per-program compile/dispatch delta for a STILL-RECORDING job
    (the overlay accounting — activity actually routed to this job).

    The ``/jobs`` table and the resident server's warm-compile evidence
    read this mid-run without closing the job's observatory window;
    ``Obs.finish_xprof`` keeps owning the end-of-job export.  Returns
    ``{}`` for a job whose window never opened (or already closed)."""
    base = getattr(obs, "xprof_base", None)
    if base is None:
        return {}
    local = LEDGER.overlay(obs)
    if local is None:
        return {}
    return LEDGER.job_delta(base, local)


def observed_jit(name: str, fn, tag=None, chunks_of=None) -> ObservedJit:
    """Observe an already-jitted callable under a stable program name.
    The name is the join key for everything downstream — compile counts,
    recompile causes, cost/MFU rows, the ``obs xprof`` table, and the
    ledger gate — so it must be stable across runs (no per-job salt).
    ``chunks_of(args...) -> int`` declares how many logical chunks one
    dispatch retires (scan-batched programs), for per-chunk dispatch-gap
    attribution."""
    return ObservedJit(name, fn, tag=tag, chunks_of=chunks_of)

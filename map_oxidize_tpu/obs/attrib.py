"""Wall-clock attribution ledger: where did every millisecond go.

The obs stack can scrape, gate, and alert on everything, yet until this
module it could not answer the first question of any perf or capacity
investigation: *what fraction of the job's wall was host produce vs
pipeline stall vs dispatch overhead vs device compute vs collective
wait vs spill I/O* — the counters were point signals (a dispatch-gap
histogram here, a feed-wait counter there) that never summed to the
wall.  This module assembles them into a decomposition that does:

* every bucket is **critical-path** time measured on the job's consumer
  side, so buckets are disjoint by construction and their sum can never
  exceed the wall — the gap between the sum and the measured wall is
  reported as the ``unattributed`` remainder (python/framework
  overhead), never hidden;
* the decomposition is computed live (each time-series tick refreshes
  the ``attrib/*`` gauges, the heartbeat's ``where=`` token, and the
  ``/status`` payload) and finalized at ``Obs.finish`` into the metrics
  document's ``attrib`` section, the flat ``attrib/*_ms`` /
  ``attrib/unattributed_pct`` gauges the run ledger stores, and
  BENCH_DETAIL snapshots;
* ``obs diff --gate`` flags an unattributed-fraction regression (the
  remainder growing means measurement coverage decayed — exactly the
  silent rot this ledger exists to prevent), see
  :mod:`map_oxidize_tpu.obs.ledger`.

Bucket definitions (ms on the job's critical path):

``setup``
    ``Obs`` creation to the first phase span: config/engine/backend
    bring-up (the ``job/setup`` span, ``span/job/setup_s``).
``host_produce``
    Host production work that ran ON the critical path: the ``split``
    chunk-planning phase plus explicitly measured inline produce (the
    auto-B fault-in probe, serial-mode produce).  In a pipelined run the
    steady-state produce is hidden in the prefetch thread — its visible
    residue is ``feed_wait``.
``feed_wait``
    Consumer stalls waiting on the prefetch/staging pipeline
    (``pipeline/feed_wait_ms``, fed live per chunk).
``host_stage``
    Host work inside the per-block engine feed that is not dispatch,
    compile, sampled compute, or spill I/O: pad/pack/``device_put``
    staging (derived: ``feed_block_ms`` total minus those, clamped at
    zero so over-subtraction can only under-attribute, never double
    count).
``dispatch_gap``
    Host handoff -> async return of every non-compiling observed
    dispatch (``device/dispatch_gap_ms``), minus the ``dist/flag_psum``
    program's share, which ``collective_wait`` owns.
``device_compute``
    The sampled ``block_until_ready`` waits the observatory actually
    paid (``device/compute_ms``) — the consumer-visible device time;
    compute hidden behind host work shows up as backpressure in the
    next dispatch's gap, already counted.
``collective_wait``
    Host-synchronous lockstep waits on the slowest participant
    (``dist/flag_wait_ms`` — the distributed flag psum, fetch
    included).
``spill_io``
    Disk-bucket shuffle spill writes and drains (``spill/io_ms``).
``host_sort``
    Host-side dataflow finalize compute on the critical path — the
    lexsorts, join probe expansion, session gap cuts, and ordered drain
    writes of the sort/join/sessionize drivers (``attrib/host_sort_ms``;
    the measuring windows subtract any spill I/O paid inside them, which
    ``spill_io`` owns, so the buckets stay disjoint).
``compile``
    Wall of compiling dispatches (trace + XLA backend compile), from
    the job's compile-ledger window.
``host_write``
    The host-only ``write`` output phase.

See docs/OBSERVABILITY.md "Where did the time go" for reading guidance.
"""

from __future__ import annotations

import time

ATTRIB_SCHEMA = "moxt-attrib-v1"

#: bucket order for reports (stable, most-upstream first)
BUCKETS = ("setup", "host_produce", "feed_wait", "host_stage",
           "dispatch_gap", "device_compute", "collective_wait",
           "spill_io", "host_sort", "compile", "host_write")

#: short spellings for the heartbeat's one-token ``where=`` field
SHORT = {
    "setup": "setup", "host_produce": "produce", "feed_wait": "wait",
    "host_stage": "stage", "dispatch_gap": "dispatch",
    "device_compute": "compute", "collective_wait": "comms",
    "spill_io": "spill", "host_sort": "sort", "compile": "compile",
    "host_write": "write", "unattributed": "other",
}

#: ``obs diff --gate``: an unattributed fraction growing by more than
#: this many percentage points over the previous comparable run flags
#: (coverage decay is a regression of the measurement plane itself)
UNATTRIBUTED_GATE_POINTS = 10.0

#: host-only phases attributed wholesale (no device dispatch ever runs
#: inside them — ``replay`` and the finalize family do dispatch, so
#: they are deliberately NOT here and contribute via the metric-derived
#: buckets instead).  ``sample`` is the sort driver's splitter-sampling
#: phase: a pure host strided read, host produce by definition.
_PRODUCE_PHASES = ("split", "sample")
_WRITE_PHASES = ("write",)


def _hist_total_ms(registry, name: str) -> float:
    h = registry.histograms.get(name)
    return float(h.total) if h is not None else 0.0


def _programs_of(obs) -> dict:
    """Per-program compile/dispatch rows for a LIVE job window (the
    compile-ledger overlay).  ``{}`` once the window closed — finish
    passes the final report's rows explicitly instead."""
    from map_oxidize_tpu.obs.compile import job_overlay_delta

    return job_overlay_delta(obs)


def compute(obs, programs: dict | None = None,
            elapsed_s: float | None = None) -> dict:
    """The attribution document: wall, per-bucket ms + pct, remainder.

    ``programs`` is the per-program compile/dispatch row map (the live
    overlay when None; ``Obs.finish`` passes the closed window's report
    rows).  ``elapsed_s`` overrides the wall (finish passes the final
    figure; live callers default to now - wall_start)."""
    if programs is None:
        programs = _programs_of(obs)
    if elapsed_s is None:
        elapsed_s = max(time.time() - obs.tracer.wall_start, 1e-9)
    wall_ms = elapsed_s * 1e3

    reg = obs.registry
    with reg._lock:
        counters = dict(reg.counters)
        phases = dict(reg.phases)
        gap_ms = _hist_total_ms(reg, "device/dispatch_gap_ms")
        compute_ms = _hist_total_ms(reg, "device/compute_ms")
        flag_wait_ms = _hist_total_ms(reg, "dist/flag_wait_ms")
        feed_block_ms = _hist_total_ms(reg, "feed_block_ms")

    compile_ms = (sum(r.get("compile_ms", 0.0) or 0.0
                      for r in programs.values())
                  # the observatory's own cost-analysis lowering wall
                  # (paid outside the timed compiling call)
                  + float(counters.get("attrib/lowering_ms", 0.0)))
    # the flag psum's dispatch walls belong to collective_wait (its
    # host-synchronous fetch wall is measured around the same calls)
    flag_gap_ms = (programs.get("dist/flag_psum") or {}).get(
        "dispatch_ms", 0.0) or 0.0
    spill_io = float(counters.get("spill/io_ms", 0.0))
    feed_wait = float(counters.get("pipeline/feed_wait_ms", 0.0))

    buckets = {
        # pre-first-phase wall (the job/setup span) plus in-phase
        # framework bring-up measured at known choke points (mesh/
        # backend init inside a streamed fit).  The SOURCES live under
        # their own names; the published attrib/setup_ms gauge is this
        # bucket's output and must never feed back in
        "setup": (float(counters.get("span/job/setup_s", 0.0)) * 1e3
                  + float(counters.get("attrib/init_ms", 0.0))),
        "host_produce": (
            float(counters.get("attrib/probe_ms", 0.0))
            + sum(phases.get(p, 0.0) for p in _PRODUCE_PHASES) * 1e3),
        "feed_wait": feed_wait,
        "host_stage": max(
            0.0, feed_block_ms - gap_ms - compute_ms - spill_io
            - compile_ms),
        "dispatch_gap": max(0.0, gap_ms - flag_gap_ms),
        "device_compute": compute_ms,
        "collective_wait": flag_wait_ms,
        "spill_io": spill_io,
        "host_sort": float(counters.get("attrib/host_sort_ms", 0.0)),
        "compile": compile_ms,
        "host_write": sum(phases.get(p, 0.0)
                          for p in _WRITE_PHASES) * 1e3,
    }
    attributed = sum(buckets.values())
    unattributed = max(0.0, wall_ms - attributed)
    doc = {
        "schema": ATTRIB_SCHEMA,
        "wall_ms": round(wall_ms, 3),
        "attributed_ms": round(attributed, 3),
        "unattributed_ms": round(unattributed, 3),
        "unattributed_pct": round(100.0 * unattributed
                                  / max(wall_ms, 1e-9), 2),
        "buckets": {
            name: {"ms": round(ms, 3),
                   "pct": round(100.0 * ms / max(wall_ms, 1e-9), 2)}
            for name, ms in buckets.items()},
    }
    return doc


def where_token(doc: dict) -> str:
    """The heartbeat's one-token live answer, e.g. ``compute 61%``: the
    largest bucket (the unattributed remainder competes as ``other``)."""
    best_name, best_pct = "unattributed", doc["unattributed_pct"]
    for name, row in doc["buckets"].items():
        if row["pct"] > best_pct:
            best_name, best_pct = name, row["pct"]
    return f"{SHORT.get(best_name, best_name)} {best_pct:.0f}%"


def publish(obs, doc: dict) -> None:
    """Flatten the document onto the registry — the gauges the time
    series, ``/metrics``, the run ledger, and BENCH_DETAIL carry — and
    refresh the heartbeat's ``where=`` token."""
    reg = obs.registry
    for name, row in doc["buckets"].items():
        reg.set(f"attrib/{name}_ms", row["ms"])
    reg.set("attrib/wall_ms", doc["wall_ms"])
    reg.set("attrib/unattributed_ms", doc["unattributed_ms"])
    reg.set("attrib/unattributed_pct", doc["unattributed_pct"])
    hb = obs.heartbeat
    if hb is not None:
        hb.where = where_token(doc)


def live_update(obs) -> dict:
    """One live refresh (each time-series tick calls this): compute from
    the running overlay, publish the gauges + heartbeat token, return
    the document (the ``/status`` payload's ``attrib`` section)."""
    doc = compute(obs)
    publish(obs, doc)
    return doc


def finalize(obs, xprof_report: dict | None,
             elapsed_s: float) -> dict:
    """The end-of-job attribution (``Obs.finish`` and the flight
    recorder): computed from the CLOSED observatory window's per-program
    rows, published, and returned for the metrics document."""
    programs = (xprof_report or {}).get("programs") or {}
    doc = compute(obs, programs=programs, elapsed_s=elapsed_s)
    publish(obs, doc)
    return doc


# --- rendering (the `obs where` report / `obs top` panel) ------------------


def render(doc: dict, title: str = "where did the time go") -> str:
    """Human-readable bucket table (the ``obs where`` stdout and the
    ``obs top`` panel body).  Pure, so tests pin it without a server."""
    wall_s = doc.get("wall_ms", 0.0) / 1e3
    lines = [f"{title}: wall {wall_s:.3f}s, "
             f"{100.0 - doc.get('unattributed_pct', 0.0):.1f}% attributed"]
    rows = [(name, row["ms"], row["pct"])
            for name, row in (doc.get("buckets") or {}).items()]
    rows.append(("unattributed", doc.get("unattributed_ms", 0.0),
                 doc.get("unattributed_pct", 0.0)))
    width = max(len(n) for n, _m, _p in rows)
    for name, ms, pct in sorted(rows, key=lambda r: -r[1]):
        bar = "#" * min(int(round(pct / 2.5)), 40)
        lines.append(f"  {name:<{width}} {ms / 1e3:>9.3f}s {pct:>5.1f}%  "
                     f"{bar}")
    return "\n".join(lines)

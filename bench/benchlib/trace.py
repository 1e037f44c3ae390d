"""Reduce a ``jax.profiler`` trace (``.xplane.pb``) to device busy time,
idle gaps and time per XLA op and module.

What it reads, as a TPU trace holds it (looked at by hand, PERF.md §3):
one plane per chip, ``/device:TPU:<n>``, with a line ``XLA Ops`` (one
event per op run, named by the HLO instruction's text,
``%fusion.17 = (bf16[20000000]...) fusion(...)``; a ``while`` op holds
the ops of its body) and a line ``XLA Modules`` (one event per program
run, named by the jitted module and a program id,
``jit_fit(10972550529845953895)``).

The jobs come from the harness's host clock, as offsets from the
moment before ``start_trace``: the profiler's event times count from
there (PERF.md §3 gives the offset measured between the two).  The
traced window runs from the start of the first job to the end of the
last.  Busy time is the union of the op intervals on a chip, clipped to
the window; the idle gaps are its complement on every chip of the
cell, named by the job they fall in.
"""

from __future__ import annotations

import re
from dataclasses import dataclass, field

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
#: ``jit_fit(123)`` -> ``jit_fit``: the module name without program id
_PROGRAM_ID = re.compile(r"\(\d+\)$")


def module_name(event_name: str) -> str:
    return _PROGRAM_ID.sub("", event_name)


@dataclass
class Reduction:
    #: traced window, seconds
    window_s: float
    #: union of op intervals, seconds, mean over the chips used
    busy_s: float
    #: per chip: busy seconds
    busy_by_device: list[float]
    #: ``module/op`` -> seconds of the ops that hold no other op, mean
    #: over the chips used
    op_s: dict[str, float] = field(default_factory=dict)
    #: module name (program id stripped) -> seconds, mean over chips
    module_s: dict[str, float] = field(default_factory=dict)
    #: (label, seconds) of the idle gaps in which no chip ran an op
    gaps: list[tuple[str, float]] = field(default_factory=list)
    #: annotated jobs in the window
    jobs: int = 0

    def ops_matching(self, pattern: str) -> float:
        rx = re.compile(pattern)
        return sum(v for k, v in self.op_s.items() if rx.search(k))

    def modules_matching(self, pattern: str) -> float:
        rx = re.compile(pattern)
        return sum(v for k, v in self.module_s.items() if rx.search(k))

    def breakdown(self, n: int = 10) -> dict:
        ops = sorted(self.op_s.items(), key=lambda kv: -kv[1])[:n]
        gaps = sorted(self.gaps, key=lambda g: -g[1])[:n]
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[k, v] for k, v in gaps]}


def _union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def op_name(event_name: str) -> str:
    """``%fusion.2 = u32[...] fusion(...)`` -> ``fusion.2``."""
    head = event_name.split(" = ", 1)[0]
    return head[1:] if head.startswith("%") else head


def reduce_planes(planes, chips: int,
                  jobs: list[tuple[float, float, str]]) -> Reduction:
    """``planes``: objects with ``name`` and ``lines``, each line with
    ``name`` and ``events`` (``name``, ``start_ns``, ``duration_ns``),
    as ``jax.profiler.ProfileData`` gives them; ``jobs``: ``(start_ns,
    end_ns, name)`` on the trace's clock."""
    devices = {int(m.group(1)): plane for plane in planes
               if (m := DEVICE_PLANE.match(plane.name))}
    if not jobs:
        raise ValueError("no job in the traced stretch")
    if len(devices) < chips:
        raise ValueError(f"the trace holds {len(devices)} TPU planes, the "
                         f"cell uses {chips}")
    lo = min(s for s, _, _ in jobs)
    hi = max(e for _, e, _ in jobs)
    busy, op_s, module_s = [], {}, {}
    every: list[tuple[float, float]] = []
    for idx in sorted(devices)[:chips]:
        lines = {line.name: line for line in devices[idx].lines}
        mods = []
        for ev in getattr(lines.get(MODULES_LINE), "events", []):
            s, e = max(ev.start_ns, lo), min(ev.start_ns + ev.duration_ns, hi)
            if e > s:
                name = module_name(ev.name)
                module_s[name] = module_s.get(name, 0.0) + (e - s) / chips
                mods.append((s, e, name))
        mods.sort()
        ops = []
        for ev in getattr(lines.get(OPS_LINE), "events", []):
            s, e = max(ev.start_ns, lo), min(ev.start_ns + ev.duration_ns, hi)
            if e > s:
                ops.append((s, e, op_name(ev.name)))
        ops.sort(key=lambda o: (o[0], -o[1]))
        # an op that holds others (a while loop around its body) is not
        # counted by name: its children are
        for i, (s, e, name) in enumerate(ops):
            if i + 1 < len(ops) and ops[i + 1][0] < e and ops[i + 1][1] <= e:
                continue
            owner = [m for ms, me, m in mods if ms <= s < me]
            key = f"{owner[-1] if owner else '?'}/{name}"
            op_s[key] = op_s.get(key, 0.0) + (e - s) / chips
        u = _union([(s, e) for s, e, _ in ops])
        busy.append(sum(e - s for s, e in u) * 1e-9)
        every += u
    gaps = []
    t = lo
    for s, e in _union(every) + [(hi, hi)]:
        if s > t:
            mid = (s + t) / 2
            owner = [name for js, je, name in jobs if js <= mid < je]
            gaps.append((f"{owner[0] if owner else 'between jobs'} "
                         f"+{(t - lo) * 1e-9:.6f}s", (s - t) * 1e-9))
        t = max(t, e)
    return Reduction(
        window_s=(hi - lo) * 1e-9, busy_s=sum(busy) / len(busy),
        busy_by_device=busy,
        op_s={k: v * 1e-9 for k, v in op_s.items()},
        module_s={k: v * 1e-9 for k, v in module_s.items()},
        gaps=gaps, jobs=len(jobs))


def reduce_trace(path: str, chips: int,
                 jobs: list[tuple[float, float, str]]) -> Reduction:
    from jax.profiler import ProfileData

    return reduce_planes(ProfileData.from_file(path).planes, chips, jobs)

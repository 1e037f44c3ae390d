"""Device reduce, the host blocked on it: the self time of the
engine's overflow and growth syncs (``runtime/engine.py``) and of the
compile observatory's sampled ``block_until_ready``
(``obs/compile.py``), mean per job."""

SPANS = ("engine/health_sync", "engine/growth_sync", "xprof/ready_wait")


def read(run):
    parts = [run.mean_job(f"span/{s}_self_s") for s in SPANS]
    parts = [p for p in parts if p is not None]
    return sum(parts) if parts else None

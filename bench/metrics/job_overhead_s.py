"""Entry, planning and the end of a job (``obs/__init__.py``): the
self time of ``job/setup`` (``Obs`` creation to the first phase: plan,
engine, audit) and ``job/finish`` (attribution, critical path, plan
scoring, calibration, memory watermarks), mean per job."""

SPANS = ("job/setup", "job/finish")


def read(run):
    parts = [run.mean_job(f"span/{s}_self_s") for s in SPANS]
    parts = [p for p in parts if p is not None]
    return sum(parts) if parts else None

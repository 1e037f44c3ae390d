"""Data-plane audit (``obs/dataplane.py``): the self time of the
``audit/fold_in`` spans (each fed chunk's conservation and skew digest,
``runtime/driver.py`` ``_ingest``) and of ``audit/fold_out`` (the check
after finalize), mean per job."""

SPANS = ("audit/fold_in", "audit/fold_out")


def read(run):
    parts = [run.mean_job(f"span/{s}_self_s") for s in SPANS]
    parts = [p for p in parts if p is not None]
    return sum(parts) if parts else None

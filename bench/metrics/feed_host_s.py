"""Device feed, host side (``runtime/driver.py`` ``_ingest``,
``runtime/engine.py``): the self time of the spans between a mapped
chunk and the device — the dictionary merge, the per-chunk feed, the
flush, each batch's pad, pack and ``device_put``, and the merge call's
dispatch — mean per job."""

SPANS = ("feed/dictionary", "engine/feed_block", "engine/flush",
         "engine/put", "engine/dispatch")


def read(run):
    parts = [run.mean_job(f"span/{s}_self_s") for s in SPANS]
    parts = [p for p in parts if p is not None]
    return sum(parts) if parts else None

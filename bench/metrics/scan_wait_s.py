"""Host map: seconds the device feed waited on the host scan
(``pipeline/feed_wait_ms``, ``runtime/pipeline.py``), mean per job."""


def read(run):
    return run.mean_job("pipeline/feed_wait_ms", 1e-3)

"""Finalize: readback of the accumulator and the top-k
(``time/finalize_s``), mean per job."""


def read(run):
    return run.mean_job("time/finalize_s")

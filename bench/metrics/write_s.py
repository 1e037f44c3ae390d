"""Write: ``final_result.txt`` (``time/write_s``), mean per job."""


def read(run):
    return run.mean_job("time/write_s")

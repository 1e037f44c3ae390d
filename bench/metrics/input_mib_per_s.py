"""Throughput, HiBench's "input size / duration": input bytes of every
job that returned in the window over the whole window (host clock)."""

MiB = 1 << 20


def read(run):
    if not run.window_s or not run.records:
        return None
    done = sum(r["input_bytes"] for r in run.records if not r.get("failed"))
    return done / MiB / run.window_s

"""Exchange: the all-to-all ops' share of their ICI roofline.

Measured time: the device time of the ops named ``all-to-all`` per
traced job, mean over the cell's chips, from the trace's op names (as
``exchange_collective_s`` reads them).  Least time: the bytes one chip
must send to the other chips in a job, over the chip's published ICI
bandwidth.  Each exchange moves ``shuffle/all_to_all_bytes`` =
S·S·cap·(8 + value bytes) in all (``parallel/shuffle.py``
``exchange_payload_bytes``); one chip sends (S − 1)/S² of it off the
chip, the rest of its buffer being the block it keeps.  The job's
counter sums its exchanges.  Counting only what a chip sends, against
the chip's whole ICI bandwidth, keeps the share low rather than above
100%.  Nothing to read where the exchange ran as ``all_gather``, or
with no trace or no counter."""

#: one v5e chip's chip-to-chip interconnect, "Interchip Interconnect BW
#: 1,600 Gbps" (Google Cloud documentation, "TPU v5e"): 200 GB/s
ICI_BYTES_PER_S = 1600e9 / 8

#: the exchange's all-to-all ops after the ``module/`` prefix
#: (``all_to_all.19`` as the v5e compiler names them, PR 22)
OPS = r"/all[-_]to[-_]all"


def off_chip_bytes(payload_bytes: float, shards: int) -> float:
    """Bytes one chip sends to the others, of ``payload_bytes`` moved by
    all ``shards`` chips together."""
    return payload_bytes * (shards - 1) / shards ** 2


def roofline(payload_bytes: float, shards: int, seconds: float) -> float:
    """Share (%) of the least time in the measured one."""
    return 100.0 * off_chip_bytes(payload_bytes, shards) / ICI_BYTES_PER_S / seconds


def read(run):
    t = run.trace
    if t is None or not run.traced_jobs:
        return None
    methods = {j.get("shuffle/exchange_collective") for j in run.jobs}
    if methods != {"all_to_all"}:
        return None
    payload = run.mean_job("shuffle/all_to_all_bytes")
    seconds = t.ops_matching(OPS) / run.traced_jobs
    if not payload or seconds <= 0:
        return None
    return roofline(payload, int(run.config["deployment"]["num_shards"]),
                    seconds)

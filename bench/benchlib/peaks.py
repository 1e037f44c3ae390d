"""Published peaks of each chip, keyed by JAX's ``device_kind``.

Copied from ``map_oxidize_tpu/obs/xprof.py`` ``PEAKS`` (PR 21).  Source:
Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 819 GB/s HBM.
A kind missing here is an error: the harness does not run on it.
"""

PEAKS = {
    "TPU v5 lite": {"flops": 197e12, "membw": 819e9},
}


def peaks_for(kind: str) -> dict:
    try:
        return PEAKS[kind]
    except KeyError:
        raise KeyError(f"no published peaks for device_kind {kind!r}; "
                       "add them to bench/benchlib/peaks.py") from None

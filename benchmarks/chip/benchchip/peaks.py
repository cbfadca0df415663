"""Published peaks of each accelerator, keyed by the ``device_kind`` JAX
reports. A device that is not in the table is an error, never a default."""

#: source: Google Cloud documentation, "TPU v5e" (system architecture):
#: 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s per chip
PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9,
                    "source": "Google Cloud documentation, TPU v5e"},
}


def peaks_of(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; add it to PEAKS with its "
                       f"source") from None


def least_time(flops: float, nbytes: float, peaks: dict) -> float:
    """The least seconds a chip can take for the work: the larger of the
    compute bound and the memory bound."""
    return max(flops / peaks["bf16_flops"], nbytes / peaks["hbm_bytes_per_s"])

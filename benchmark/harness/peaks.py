"""Published peaks of the chips the benchmark may run on, keyed by the
`device_kind` jax reports. One table; a kind that is not in it is an error,
never a default.

Source: Google Cloud documentation, "TPU v5e" system architecture page
(197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2e at 819 GB/s per chip). Copied
from the program's `cost_model/analytical.py` `DEVICES` so that a later PR
that edits the program cannot move the yardstick.
"""

PEAKS = {
    "TPU v5 lite": {"name": "TPU v5e", "flops_bf16": 197e12,
                    "ops_int8": 393e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9},
    "TPU v5e": {"name": "TPU v5e", "flops_bf16": 197e12,
                "ops_int8": 393e12, "hbm_bytes_per_s": 819e9,
                "hbm_bytes": 16e9},
}


def peaks_for(device_kind):
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"add a row with its source to benchmark/harness/"
                       f"peaks.py (known: {sorted(PEAKS)})")
    return PEAKS[device_kind]

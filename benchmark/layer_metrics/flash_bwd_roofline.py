"""The flash backward's share of its roofline in the train step: the least
time for the causal backward at the step's (B, H, S, D), a layer a step,
over the device time of the operations named `flash_dq` and `flash_dkv`."""
from benchmark.harness import kernel_names


def read(record, trace):
    return kernel_names.roofline_pct(record, trace, "bwd", "flash_dq",
                                     "flash_dkv")

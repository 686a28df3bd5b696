"""The flash forward's share of its roofline in the train step: the least
time for the causal forward at the step's (B, H, S, D), a layer a step, over
the device time of the operations named `flash_fwd`. Under `remat="dots"` a
layer runs the forward twice; the second run is time spent, not work
needed, as `flash_roofline` already rules."""
from benchmark.harness import kernel_names


def read(record, trace):
    return kernel_names.roofline_pct(record, trace, "fwd", "flash_fwd")

"""Share of the latent rows a decode step's attention reads that are
resident rows of the active slots: `latent_rows_held / latent_rows_read`
summed over the window's `serving::decode.wait` spans (all latent layers).
Under the gather arm a step builds the dense view of every slot's whole
table, so the share is the pool's fill; a decode path that reads only what
a slot holds reads 100. None from a program that does not count the rows."""
from benchmark.harness import program_counters

KEYS = ("latent_rows_read", "latent_rows_held")


def read(record, trace):
    rows = program_counters.attr_sums(record, "decode.wait", KEYS)
    if not rows or not rows["latent_rows_read"]:
        return None
    return 100.0 * rows["latent_rows_held"] / rows["latent_rows_read"]

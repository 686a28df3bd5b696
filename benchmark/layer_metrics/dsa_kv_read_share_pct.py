"""Share of the K/V rows the decode read that attention selected, summed
over the window's `serving::decode.wait` spans (all selecting layers): each
carries the host's `dsa_rows_selected` of the call it waited for and the
program's own `dsa_kv_rows_read`, which the decode counts from the shape of
the rows it hands to attention. The read by position fetches `topk` rows a
slot whatever the slot holds, so it reads 100 when every slot is busy past
`topk` positions and only the selection is read; a decode that built each
slot's dense view would count the view's rows and read the share of the view
that is selected. None from a program that does not count the rows."""
from benchmark.harness import program_counters

KEYS = ("dsa_rows_selected", "dsa_kv_rows_read")


def read(record, trace):
    rows = program_counters.attr_sums(record, "decode.wait", KEYS)
    if not rows or not rows["dsa_kv_rows_read"]:
        return None
    return 100.0 * rows["dsa_rows_selected"] / rows["dsa_kv_rows_read"]

"""Median over the window's prefills of what the host adds to one:
`serving::prefill.admit` + `serving::prefill` + `serving::prefill.publish`
minus the call's in-flight interval (start of `serving::prefill.dispatch` to
the end of `serving::prefill.wait`), off the span log (harness/host_gaps.py).
The launch and the fetch's tail lie inside that interval and are not in it."""
from benchmark.harness import host_gaps


def read(record, trace):
    return host_gaps.prefill_host_ms_p50(record)

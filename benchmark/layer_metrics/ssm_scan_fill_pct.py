"""Share of the positions the state layers' chunked prefill scan ran that
were a real token and not bucket padding: `ssm_tokens_valid /
ssm_tokens_scanned` summed over the window's `serving::prefill` spans. What
the prefill ladder's padding costs the scan: 100 would be a bucket a
prompt. None from a program that does not count them."""
from benchmark.harness import program_counters

KEYS = ("ssm_tokens_scanned", "ssm_tokens_valid")


def read(record, trace):
    scan = program_counters.attr_sums(record, "prefill", KEYS)
    if not scan or not scan["ssm_tokens_scanned"]:
        return None
    return 100.0 * scan["ssm_tokens_valid"] / scan["ssm_tokens_scanned"]

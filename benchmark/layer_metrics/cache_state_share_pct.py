"""Share of the cache bytes in use that are per-slot recurrent state (and
convolution tails) and not paged latent rows: `state_bytes / (state_bytes +
latent_bytes_in_use)` from the `serving::step` span's attrs, mean over the
window's steps that decoded. Near 100 the block pool is not where this
model's memory goes."""
from benchmark.harness import program_spans


def read(record, trace):
    def share(step):
        a = step["attrs"]
        total = a.get("state_bytes", 0) + a.get("latent_bytes_in_use", 0)
        if "decode_step" not in step["total_ns"] or "state_bytes" not in a \
                or not total:
            return None
        return a["state_bytes"] / total
    return program_spans.step_mean_pct(record, share)

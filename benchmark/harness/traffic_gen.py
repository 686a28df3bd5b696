"""The one general generator of serving traffic: a data file of parameters
in, requests out.

Every seed gets the SAME multiset of (prompt length, output length) pairs —
`sizes` of them, laid on the quantiles of the two distributions the mix
states — in another order, and its own token ids. So a seed changes which
request meets which, not how much work there is. The order is shuffled in
strata: each round of `clients` requests takes one pair from each band of
prompt lengths, so a window that ends part-way through a cycle has still seen
every band equally often (measured, PR 25: with a plain shuffle the median
time to first token moved 6 % from seed to seed and 0.5 % from run to run).
"""
import numpy as np


def quantile_lengths(dist, n):
    """`n` whole lengths on the mid-quantiles of a distribution
    {"dist": "uniform" | "loguniform", "lo", "hi"} (both ends included)."""
    u = (np.arange(n) + 0.5) / n
    lo, hi = float(dist["lo"]), float(dist["hi"])
    if dist["dist"] == "uniform":
        x = lo + u * (hi - lo)
    elif dist["dist"] == "loguniform":
        x = lo * (hi / lo) ** u
    else:
        raise ValueError(f"unknown distribution {dist['dist']!r}")
    return np.clip(np.rint(x), lo, hi).astype(int)


def size_pairs(mix):
    """The mix's fixed multiset of (prompt, output) lengths. The pairing is
    a fixed shuffle (not the run's seed), so long prompts meet short and long
    outputs alike."""
    n = mix["sizes"]
    prompts = quantile_lengths(mix["prompt_len"], n)
    outputs = quantile_lengths(mix["output_len"], n)
    outputs = outputs[np.random.default_rng(20250925).permutation(n)]
    return list(zip(prompts.tolist(), outputs.tolist()))


class ClosedLoopTraffic:
    """`clients` callers, each walking its own slice of the seed's order of
    the size pairs, cycle after cycle; token ids drawn below `draw_vocab`."""

    def __init__(self, mix, draw_vocab, seed):
        self.pairs = size_pairs(mix)
        self.clients = mix["clients"]
        self.draw_vocab = draw_vocab
        self.rng = np.random.default_rng([int(seed), 0x5e12])
        self.order = []
        self.cursor = 0

    def _cycle(self):
        """One pass over all the pairs: bands of prompt length (as many as
        there are clients), one pair of each band per round, bands and pairs
        in the seed's order."""
        by_length = sorted(range(len(self.pairs)),
                           key=lambda i: self.pairs[i])
        bands = [self.rng.permutation(b).tolist()
                 for b in np.array_split(by_length, self.clients)]
        order = []
        for r in range(max(len(b) for b in bands)):
            round_ = [b[r] for b in bands if r < len(b)]
            order.extend(self.rng.permutation(round_).tolist())
        return order

    def next_request(self):
        """(prompt ids, output length) of the next request anyone sends."""
        if self.cursor == len(self.order):
            self.order = self._cycle()
            self.cursor = 0
        plen, olen = self.pairs[self.order[self.cursor]]
        self.cursor += 1
        prompt = self.rng.integers(0, self.draw_vocab, plen).tolist()
        return prompt, olen

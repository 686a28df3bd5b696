"""A closed-loop cell on a virtual clock: which of its end-to-end metrics
can be steady from seed to seed, before any chip time is spent.

    python3 benchmark/tools/sim_closed_loop.py <traffic> --ladder 128,192,384,768,1024,2048 \\
        --decode-ms 29 --prefill-ms 16 --prefill-ms-per-token 0.0708 [--seeds 48]

The loop of loops/closed_loop_model.py (run-in, then a 30 s window) with a
step that costs one decode plus an affine prefill for every request it
admits; the costs are read off chip runs (PERF.md section 6, PR 28, fitted
them to two sets). Prints each metric's median and range and the quartile
spread of every set of six seeds: a percentile that sits on the edge of a
prefill bucket shows as a spread far over its bound. No device, no jax.
"""
import argparse
import json
import os
import statistics
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.harness import traffic_gen  # noqa: E402


def run(mix, seed, ladder, decode_s, prefill_s, per_token_s, window_s=30.0):
    traffic = traffic_gen.ClosedLoopTraffic(
        dict(mix, clients=mix.get("order_bands", mix["clients"])), 1000, seed)
    now, requests, clients = 0.0, [], [None] * mix["clients"]

    def submit(i, phase):
        prompt, want = traffic.next_request()
        clients[i] = {"bucket": next(b for b in ladder if len(prompt) <= b),
                      "want": want, "submit": now, "stamps": [],
                      "phase": phase}
        requests.append(clients[i])

    for i in range(len(clients)):
        submit(i, "run_in")
    first_wave, phase, t0, t_end = list(requests), "run_in", None, None
    while True:
        now += decode_s + sum(prefill_s + per_token_s * r["bucket"]
                              for r in clients if not r["stamps"])
        for i, r in enumerate(clients):
            r["stamps"].append(now)
            if len(r["stamps"]) >= r["want"]:
                r["done"] = True
                submit(i, phase)
        if phase == "run_in" and all("done" in r for r in first_wave):
            phase, t0, t_end = "window", now, now + window_s
        elif phase == "window" and now >= t_end:
            break
    ttft = [r["stamps"][0] - r["submit"] for r in requests
            if r["phase"] == "window" and r["stamps"]]
    stamps = [(s, r["stamps"][k - 1] if k else None)
              for r in requests for k, s in enumerate(r["stamps"])
              if t0 <= s <= t_end]
    gaps = [s - before for s, before in stamps
            if before is not None and before >= t0]
    return {"serve_tokens_per_s": len(stamps) / window_s,
            "ttft_p50_ms": 1e3 * statistics.median(ttft),
            "gap_p95_ms": 1e3 * statistics.quantiles(gaps, n=20)[-1]}


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("traffic")
    ap.add_argument("--ladder", required=True)
    ap.add_argument("--decode-ms", type=float, required=True)
    ap.add_argument("--prefill-ms", type=float, required=True)
    ap.add_argument("--prefill-ms-per-token", type=float, required=True)
    ap.add_argument("--seeds", type=int, default=48)
    args = ap.parse_args()
    with open(os.path.join(ROOT, "benchmark", "traffic",
                           args.traffic + ".json")) as f:
        mix = json.load(f)
    ladder = sorted(int(b) for b in args.ladder.split(","))
    rows = [run(mix, 9000 + s, ladder, args.decode_ms / 1e3,
                args.prefill_ms / 1e3, args.prefill_ms_per_token / 1e3)
            for s in range(args.seeds)]
    for name in rows[0]:
        values = [r[name] for r in rows]
        spreads = []
        for i in range(0, len(values) - 5, 6):
            q = statistics.quantiles(values[i:i + 6], n=4)
            spreads.append((q[2] - q[0]) / statistics.median(values[i:i + 6]))
        print(f"{name}: median {statistics.median(values):.1f} "
              f"[{min(values):.1f}, {max(values):.1f}]; spreads of the sets "
              f"of six: {' '.join(f'{x:.4f}' for x in spreads)}")


if __name__ == "__main__":
    main()

"""The comparison that decides `correct`.

Each number compared has a limit of its own, kept in
`benchmark/limits/<cell>.json` with the readings it was set from (PERF.md §2
has the table). A check is `{"name", "value", "limit", "ok"}`; a value that is
not finite fails.
"""
import json
import math
import os

import numpy as np

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def load_limits(cell, tiny=False):
    with open(os.path.join(HERE, "limits", f"{cell}.json")) as f:
        spec = json.load(f)
    return spec["tiny_limits"] if tiny else spec["limits"]


def check(name, value, limit):
    value = float(value)
    ok = math.isfinite(value) and value <= limit
    return {"name": name, "value": value, "limit": limit, "ok": ok}


def checks_from(readings, limits):
    """One check per limit; a reading the limits do not name is printed
    nowhere, a limit with no reading fails."""
    return [check(name, readings.get(name, float("nan")), limit)
            for name, limit in limits.items()]


# ---------------------------------------------------------------- training

def flatten_norms(norms):
    """{leaf: [per-layer norms]} -> ([names], vector)."""
    names, vals = [], []
    for leaf in sorted(norms):
        for i, x in enumerate(np.asarray(norms[leaf]).reshape(-1)):
            names.append(f"{leaf}[{i}]")
            vals.append(float(x))
    return names, np.asarray(vals, np.float64)


def worst_norm_gap(got, want, keep=None):
    """Worst leaf's gap between the program's norm and the reference's (not
    the norm of their difference), against the reference's norm of that leaf
    or of the median leaf, whichever is larger. Returns (gap, leaf name)."""
    names, g = flatten_norms(got)
    names_w, w = flatten_norms(want)
    assert names == names_w, "program and reference disagree on the leaves"
    scale = np.maximum(w, np.median(w))
    gaps = np.abs(g - w) / scale
    if keep is not None:
        gaps = np.where(keep, gaps, 0.0)
    gaps = np.where(np.isfinite(gaps), gaps, np.inf)
    i = int(np.argmax(gaps))
    return float(gaps[i]), names[i]


def moved_leaves(ref_grad_norms):
    """Leaves whose reference gradient is nought to rounding (under a
    thousandth of the median leaf's) move under Adam by round-off alone: they
    are left out of the parameters' change, by this rule and not by name."""
    _, w = flatten_norms(ref_grad_norms)
    return w >= 1e-3 * np.median(w)


def train_readings_gap(got, want):
    """The numbers a training cell compares, from the program's readings
    (`got`) and the reference's (`want`): each `{"losses", "grad_norms",
    "delta_norms"}` over the same first steps."""
    out = {}
    for i, (a, b) in enumerate(zip(got["losses"], want["losses"]), 1):
        out[f"loss_gap_step{i}"] = abs(a - b) / abs(b)
    out["grad_norm_gap"], out["grad_norm_gap_leaf"] = worst_norm_gap(
        got["grad_norms"], want["grad_norms"])
    out["delta_norm_gap"], out["delta_norm_gap_leaf"] = worst_norm_gap(
        got["delta_norms"], want["delta_norms"],
        keep=moved_leaves(want["grad_norms"]))
    return out


# ----------------------------------------------------------------- serving

def served_token_gaps(ref_logits, tokens):
    """For each served token, how far its logit lies below the reference's
    best at that position. `ref_logits` [n, V] are the reference's logits at
    the positions that produced `tokens` [n]."""
    ref_logits = np.asarray(ref_logits, np.float64)
    best = ref_logits.max(axis=-1)
    picked = ref_logits[np.arange(len(tokens)), np.asarray(tokens)]
    return best - picked

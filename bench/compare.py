"""The comparison that decides ``correct``: each number the program and
the reference give, and its limit.  A check holds when its number is
finite and at most its limit.
"""
from __future__ import annotations

import numpy as np

# leaves whose reference gradient is under this share of the median leaf's
# are left out of the change after the checked steps: they move by round-off
# alone (none at the configurations' sizes, but the rule holds for any)
STILL_LEAF = 1e-3
# an element of the engine's state deviates when it misses the reference by
# more than this share of the iterate's largest magnitude (f32 round-off is
# ~1e-7 of it; a flipped 2-bit level is ~1e-1)
ELEMENT_TOL = 1e-4


def _ratio(num, den):
    """num / den with 0 / 0 = 0 and x / 0 = inf."""
    num, den = np.asarray(num, np.float64), np.asarray(den, np.float64)
    with np.errstate(divide="ignore", invalid="ignore"):
        r = np.where(den > 0, num / np.where(den > 0, den, 1.0),
                     np.where(num == 0, 0.0, np.inf))
    return np.where(np.isnan(num) | np.isnan(den), np.inf, r)


def worst_leaf_gap(prog, ref, keep=None) -> float:
    """The largest |prog - ref| over (leaf, agent) entries of a norm, each
    against the larger of its own reference norm and the median entry's."""
    prog, ref = np.asarray(prog, np.float64), np.asarray(ref, np.float64)
    if keep is not None:
        prog, ref = prog[keep], ref[keep]
    if ref.size == 0:
        return 0.0
    med = float(np.median(ref))
    return float(np.max(_ratio(np.abs(prog - ref), np.maximum(ref, med))))


def rel_gap(prog, ref) -> float:
    prog, ref = np.asarray(prog, np.float64), np.asarray(ref, np.float64)
    return float(np.max(_ratio(np.abs(prog - ref), np.abs(ref))))


def train_values(prog: dict, ref: dict) -> dict:
    """A training cell's numbers:

    loss_gap        each checked step's per-agent loss, relative;
    grad_norm_gap   each checked step's gradient norm, relative;
    first_grad_gap  the first gradient per (leaf, agent), by the worst leaf;
    change_gap      each state field's change over the checked steps per
                    (leaf, agent), by the worst leaf;
    bits_gap        each step's wire bits against the quantizer's meter.
    """
    keep = ref["first_grad"] >= STILL_LEAF * np.median(ref["first_grad"])
    return {
        "loss_gap": rel_gap(prog["loss"], ref["loss"]),
        "grad_norm_gap": rel_gap(prog["grad_norm"], ref["grad_norm"]),
        "first_grad_gap": worst_leaf_gap(prog["first_grad"],
                                         ref["first_grad"]),
        "change_gap": max(worst_leaf_gap(prog["change"][f], ref["change"][f],
                                         keep)
                          for f in ref["change"]),
        "bits_gap": float(np.max(np.abs(np.asarray(prog["bits"])
                                        - np.asarray(ref["bits"])))),
    }


def window_stall(eta: float, grad_norms, moved: float) -> float:
    """eta x the summed gradient norms that the steps after the checked ones
    reported, over the distance the iterate moved in them (from the
    reference's iterate after the checked steps to the program's at the
    end).  With one agent the LEAD iterate moves by eta x the gradient each
    step, so by the triangle inequality this is at least 1 up to round-off;
    a state that stops moving in the window sends it towards infinity."""
    return float(_ratio(eta * float(np.sum(grad_norms)), moved))


def deviating_share(prog, ref) -> float:
    """Share of the elements of the state fields that miss the reference by
    more than ELEMENT_TOL of the iterate's scale (a NaN misses)."""
    scale = max(1.0, float(np.max(np.abs(ref[0]))))
    bad = total = 0
    for p, r in zip(prog, ref):
        bad += int(np.sum(~(np.abs(p - r) <= ELEMENT_TOL * scale)))
        total += r.size
    return bad / total


def engine_values(first, ref, dist_ratio: float, bits_gap: float) -> dict:
    """An engine cell's numbers:

    deviating_share  the state after the first call against the reference;
    dist_ratio       max_i ||x_i - x*|| at the end over the start's;
    bits_gap         the first call's wire bits against the meter.
    """
    return {"deviating_share": deviating_share(first, ref),
            "dist_ratio": float(dist_ratio), "bits_gap": float(bits_gap)}


def checks(values: dict, limits: dict) -> list:
    """(name, value, limit) for every number compared; every number of the
    cell has a limit."""
    missing = set(values) - set(limits)
    if missing:
        raise KeyError(f"no limit for {sorted(missing)}")
    return [(k, float(v), float(limits[k])) for k, v in values.items()]


def holds(value: float, limit: float) -> bool:
    return bool(np.isfinite(value) and value <= limit)

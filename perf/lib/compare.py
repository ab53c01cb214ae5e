"""The comparison that decides `correct`: each number beside its limit."""

from __future__ import annotations

import math

import numpy as np


class Checks:
    """Numbers compared, each with a limit of its own. `correct` is true
    only when every value is finite and within its limit; every run prints
    all of them."""

    def __init__(self) -> None:
        self.rows = []

    def add(self, name: str, value: float, limit: float,
            note: str = "") -> None:
        ok = math.isfinite(value) and value <= limit
        self.rows.append({"name": name, "value": float(value),
                          "limit": float(limit), "ok": bool(ok),
                          "note": note})

    @property
    def correct(self) -> bool:
        return bool(self.rows) and all(r["ok"] for r in self.rows)

    def lines(self) -> list:
        return [f"check {r['name']}: {r['value']:.6g} (limit "
                f"{r['limit']:.6g}) {'ok' if r['ok'] else 'FAILED'}"
                + (f" [{r['note']}]" if r["note"] else "")
                for r in self.rows]


def worst_leaf_gap(program: np.ndarray, reference: np.ndarray) -> tuple:
    """(gap, leaf index): the largest |program norm - reference norm| over
    leaves, each measured against the reference's norm of that leaf or of
    the median leaf, whichever is larger (some gradients are all but
    zero). The gap between norms, not the norm of a difference."""
    program = np.asarray(program, np.float64)
    reference = np.asarray(reference, np.float64)
    if program.shape != reference.shape:
        raise ValueError(f"{program.shape} leaves against {reference.shape}")
    scale = np.maximum(reference, np.median(reference))
    gap = np.abs(program - reference) / np.maximum(scale, 1e-300)
    gap = np.where(np.isfinite(gap), gap, np.inf)
    i = int(np.argmax(gap))
    return float(gap[i]), i


def token_gaps(logits, picks):
    """(positions, vocab) logits and one picked token a position -> how far
    each picked token's logit lies below the best at its position (numpy
    arrays, or jax arrays inside a jitted function)."""
    return logits.max(axis=-1) - logits[np.arange(logits.shape[0]), picks]


def served_token_gaps(logits: np.ndarray, prompt_len: int,
                      served: list) -> np.ndarray:
    """For each served token, how far its reference logit lies below the
    reference's best at that position. `logits` (len, vocab) is one
    reference forward over prompt + served tokens; token j was chosen at
    position prompt_len - 1 + j."""
    rows = logits[prompt_len - 1: prompt_len - 1 + len(served)]
    return token_gaps(rows, np.asarray(served, np.int64))

"""The numbers that decide ``correct`` for a training cell, from the
program's readings and the reference's.

* ``grad1_gap``: over leaves, the largest gap between the program's and the
  reference's norm of the first gradient as the optimizer is given it
  (before its clip), as a share of the larger of the reference leaf's
  norm and the median leaf's.
* ``delta3_gap``: the same for the norm of each leaf's change after three
  steps.
* ``grad1_diff``: over leaves, the largest norm of the difference between
  the program's first gradient and the reference's, as a share of the
  same floor.  The two gaps above are blind to rounding: noise that does
  not bias a leaf adds to its norm in quadrature, so the program's bf16
  and fp8 (the control) read alike on them (``PERF.md``).  The difference
  grows with the noise itself.

``loss_gap``, the largest relative gap of the first three steps' losses,
is returned too but is not compared: bfloat16 rounding moves it as far as
the controls do (``PERF.md``).

Leaves whose reference gradient is under a thousandth of the median
leaf's (nought to rounding, such as rows of the embedding that no token
and no logit reaches) move by weight decay and round-off alone and are
left out of the leaf numbers, by that rule and not by name.
"""

from __future__ import annotations

from typing import Dict

import numpy as np

SMALL_GRAD = 1e-3


def _worst(gap: np.ndarray, ref: np.ndarray, keep: np.ndarray) -> float:
    g, r = np.asarray(gap, np.float64)[keep], np.asarray(ref, np.float64)[keep]
    return float(np.max(g / np.maximum(r, np.median(r))))


def train_gaps(prog: Dict[str, np.ndarray], ref: Dict[str, np.ndarray],
               grad1_diff: np.ndarray) -> Dict[str, float]:
    """The numbers above; ``grad1_diff`` holds the per-leaf norms of the
    difference of the two first gradients."""
    g1 = np.asarray(ref["grad1"], np.float64)
    keep = g1 >= SMALL_GRAD * np.median(g1)
    lp = np.asarray(prog["losses"], np.float64)
    lr = np.asarray(ref["losses"], np.float64)

    def gap(k):
        return np.abs(np.asarray(prog[k], np.float64)
                      - np.asarray(ref[k], np.float64))

    return {
        "leaves_left_out": int(np.sum(~keep)),
        "loss_gap": float(np.max(np.abs(lp - lr) / np.abs(lr))),
        "grad1_gap": _worst(gap("grad1"), g1, keep),
        "delta3_gap": _worst(gap("delta3"), ref["delta3"], keep),
        "grad1_diff": _worst(grad1_diff, g1, keep),
    }

"""The comparison that decides ``correct``.

Everything is compared as numbers (losses, gradients, log-probabilities),
never as an argmax: with random weights the largest logit moves on
rounding.  The tolerances belong to a configuration's numerics and stand,
with their reasons, beside its reference (``configs/<config>.py CHECKS``).
"""
from __future__ import annotations

import math


def rel_l2(got, ref) -> float:
    """||got - ref||_2 / ||ref||_2; infinite on a shape mismatch, a
    non-finite value or a zero reference."""
    import numpy as np

    got = np.asarray(got, np.float64)
    ref = np.asarray(ref, np.float64)
    if got.shape != ref.shape:
        return math.inf
    denom = float(np.linalg.norm(ref))
    if not np.all(np.isfinite(got)) or denom == 0.0:
        return math.inf
    return float(np.linalg.norm(got - ref)) / denom


def compare_training(spec, loss, grads, ref_loss, ref_grads) -> dict:
    """One entry of a configuration's ``CHECKS`` against the system's loss
    and gradients: {'ok', 'loss_rel_err', 'grad_rel_err'}.
    ``grad_rel_tol`` names the gradients that are held."""
    loss, ref_loss = float(loss), float(ref_loss)
    loss_err = abs(loss - ref_loss) / abs(ref_loss) \
        if math.isfinite(loss) and ref_loss else math.inf
    held = {n: rel_l2(grads[n], ref_grads[n]) for n in spec["grad_rel_tol"]}
    ok = loss_err <= spec["loss_rel_tol"] and \
        all(held[n] <= tol for n, tol in spec["grad_rel_tol"].items())
    return {"ok": bool(ok), "loss_rel_err": loss_err, "grad_rel_err": held}


def losses_fall(losses) -> dict:
    """The window's losses on the repeated batch: all finite, and the last
    below the first."""
    import numpy as np

    arr = np.asarray(losses, np.float64).reshape(-1)
    finite = bool(arr.size and np.all(np.isfinite(arr)))
    return {"ok": bool(finite and arr[-1] < arr[0]),
            "finite": finite, "first": float(arr[0]) if arr.size else None,
            "last": float(arr[-1]) if arr.size else None}

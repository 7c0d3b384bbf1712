"""The comparison that decides ``correct``.

Every request sent in the window is compared: its served score, as its
future resolved it, against the plain float32 reference run on the same
ids with weights made again from the seed (the program's own parameters
are gone by then). The reference's products run at the precision the
configuration states (``matmul_precision``: ``"default"`` is one bfloat16
pass with float32 accumulation on a TPU, as the program's float32 plans
run). The numbers compared, each with its limit:

    max_dscore   widest |served score − reference score| over the
                 requests; a failed, unanswered or non-finite score
                 counts as 1 (the widest gap a probability can have)
    unanswered   requests that never resolved, or failed

The limit of ``max_dscore`` is the configuration's ``limits.max_dscore``;
``unanswered`` is held to 0. ``PERF.md`` gives the readings each limit
was set from.
"""

from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

#: requests per reference call (one compiled shape; the last block pads)
BLOCK = 4096


def reference_scores(cfg: dict, ref_model, weights, ids: np.ndarray,
                     dtype=jnp.float32) -> np.ndarray:
    """``σ(logits)`` of the reference for every row of ``ids``, computed
    in blocks of ``BLOCK`` rows, in ``dtype`` (float32, or bfloat16 for the
    control), every product at the configuration's ``matmul_precision``."""
    from chipbench import refmath
    if dtype != jnp.float32:
        weights = refmath.cast(weights, dtype)

    @jax.jit
    def score(w, block):
        return jax.nn.sigmoid(ref_model.logits(cfg, w, block)) \
            .astype(jnp.float32)

    out = np.empty(len(ids), np.float32)
    with jax.default_matmul_precision(cfg["matmul_precision"]):
        for lo in range(0, len(ids), BLOCK):
            block = ids[lo:lo + BLOCK]
            n = len(block)
            if n < BLOCK:
                block = np.concatenate(
                    [block, np.zeros((BLOCK - n, ids.shape[1]), ids.dtype)])
            out[lo:lo + n] = np.asarray(
                score(weights, jnp.asarray(block)))[:n]
    return out


def compare(served: np.ndarray, ok: np.ndarray, ref: np.ndarray,
            limits: dict) -> dict:
    """The compared numbers, each ``{"value", "limit"}``. ``ok`` marks the
    requests that resolved to a score."""
    gap = np.abs(served.astype(np.float64) - ref.astype(np.float64))
    gap[~ok | ~np.isfinite(gap)] = 1.0
    return {
        "max_dscore": {"value": float(gap.max()) if gap.size else 0.0,
                       "limit": float(limits["max_dscore"])},
        "unanswered": {"value": int((~ok).sum()), "limit": 0},
    }


def control_checks(cfg: dict, ref_model, weights, ids: np.ndarray) -> dict:
    """The control in the program's place: the reference computed in
    bfloat16, the precision below the configuration's float32, answering
    ``ids``, held to the configuration's limits as a run's answers are.
    It has to come out not passed (``PERF.md`` gives its readings)."""
    ref = reference_scores(cfg, ref_model, weights, ids)
    low = reference_scores(cfg, ref_model, weights, ids, jnp.bfloat16)
    return compare(low, np.ones(len(ids), bool), ref, cfg["limits"])


def passed(checks: dict) -> bool:
    return all(c["value"] <= c["limit"] for c in checks.values())

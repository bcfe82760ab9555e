"""Bandwidth-measured calibration: fit per-level α/β from wall-time sweeps.

The α-β predictions in ``topo/model.py`` use v5e-ish constants; real
hardware should fit its own. For a level-aligned schedule on a
:class:`~repro.topo.model.Hierarchy`, each round's traffic rides exactly one
level, so a measured wall time decomposes linearly:

    wall ≈ Σ_rounds  (msgs_on_busiest_link · α_level  +
                      elems_on_busiest_link · payload · β_level)

:func:`round_features` extracts the per-round (level, msgs, elems) rows from
any lowered schedule, and :func:`fit_level_costs` least-squares a stacked
sweep (multiple algorithms × payload sizes) into one
:class:`~repro.topo.model.LinkCost` per level — ready to pass as
``Hierarchy(levels, costs=fitted)`` or compare against
``default_level_costs``. No sweep measured on the chip feeds it yet: the
topology's own constants price every schedule.
"""

from __future__ import annotations

import numpy as np

from .model import Hierarchy, LinkCost, round_link_loads


def round_features(rounds, topo: Hierarchy) -> list[dict]:
    """Per round: ``{"level": j, "msgs": a, "elems": e}`` — the busiest link
    of the round's highest occupied level (level-aligned schedules touch one
    level per round; for mixed rounds the slowest level dominates). These
    are the per-round rows the calibration fit consumes; ``elems`` is in
    schedule units (multiply by payload elements when fitting)."""
    out = []
    for msgs in rounds:
        loads = round_link_loads(topo, msgs)
        if not loads:
            continue
        top = max(link[1] for link in loads)
        cnt, elems = max(
            (v for link, v in loads.items() if link[1] == top),
            key=lambda v: (v[1], v[0]),
        )
        out.append({"level": int(top), "msgs": int(cnt), "elems": int(elems)})
    return out


def fit_level_costs(measurements, n_levels: int) -> tuple[LinkCost, ...]:
    """Least-squares (α_j, β_j) per level from measured wall times.

    ``measurements``: iterable of dicts with

    * ``"wall_s"`` — measured seconds for one (algorithm, payload) run;
    * ``"payload_elems"`` — field elements per schedule unit;
    * ``"rounds"`` — the :func:`round_features` rows of that schedule.

    Solves ``wall ≈ Σ_j A_j·α_j + E_j·β_j`` with A_j = Σ msgs over level-j
    rounds and E_j = Σ elems·payload; needs ≥ 2·n_levels independent samples
    (sweep payload sizes). Coefficients are clipped to a small positive
    floor — a physical link never has negative cost."""
    rows, y = [], []
    for m in measurements:
        feat = np.zeros(2 * n_levels)
        pay = float(m.get("payload_elems", 1))
        for r in m["rounds"]:
            j = int(r["level"])
            if not 0 <= j < n_levels:
                raise ValueError(f"round level {j} outside [0, {n_levels})")
            feat[2 * j] += r["msgs"]
            feat[2 * j + 1] += r["elems"] * pay
        rows.append(feat)
        y.append(float(m["wall_s"]))
    X = np.asarray(rows)
    y = np.asarray(y)
    if X.shape[0] < 2 * n_levels:
        raise ValueError(
            f"need ≥ {2 * n_levels} samples to fit {n_levels} levels, got {X.shape[0]}"
        )
    theta, *_ = np.linalg.lstsq(X, y, rcond=None)
    theta = np.maximum(theta, 1e-12)
    return tuple(
        LinkCost(alpha=float(theta[2 * j]), beta=float(theta[2 * j + 1]))
        for j in range(n_levels)
    )

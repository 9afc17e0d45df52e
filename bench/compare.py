"""The numbers that decide `correct`.

A Monte-Carlo estimate differs from its reference vertex by vertex by
sampling noise that no limit can separate from a fault at this walk
budget. Summed over a few large groups of vertices, the noise averages
out and what stays is bias: walks lost, cut short or sent the wrong way.
So the distribution is compared group by group, with the groups taken
by the reference's rank (the top 1/G of vertices first), where a change
of damping or a dropped tail shifts mass between the groups.
"""
from __future__ import annotations

import numpy as np

GROUPS = 16


def rank_groups(ref: np.ndarray, groups: int = GROUPS) -> np.ndarray:
    """Group id per vertex: vertices sorted by reference value, highest
    first, cut into `groups` runs of equal length."""
    order = np.argsort(-ref, kind="stable")
    gid = np.empty(len(ref), np.int64)
    gid[order] = np.arange(len(ref)) * groups // len(ref)
    return gid


def grouped_l1(est, ref, groups: int = GROUPS) -> float:
    """sum_g |sum_{v in g} (est_v - ref_v)| / sum_v ref_v, with the groups
    taken by `ref`."""
    est = np.asarray(est, np.float64)
    ref = np.asarray(ref, np.float64)
    diff = np.bincount(rank_groups(ref, groups), weights=est - ref,
                       minlength=groups)
    return float(np.abs(diff).sum() / ref.sum())

"""Numpy reference for the decision kernels: the host decision core.

The oracle the Pallas path is pinned against is simply the
existing vectorized host path — ``latency_matrix`` + row argmin for the
analytic default, and the ``CostModel`` component/scalarise pipeline for
multi-objective decisions.  Kept as a thin delegation (not a copy) so the
equivalence tests in ``tests/test_decide_split.py`` always compare the
accelerated paths against the *live* host implementation.
"""
from __future__ import annotations

from typing import Sequence

import numpy as np

from repro.core import decisions as dec
from repro.core.offload import DEFAULT_EFFICIENCY, LayerCost


def latency_matrix_ref(layers: Sequence[LayerCost], envs: dec.EnvArrays,
                       efficiency: float = DEFAULT_EFFICIENCY) -> np.ndarray:
    """``[E, L+1]`` total-latency matrix, host numpy."""
    return dec.latency_matrix(layers, envs, efficiency)


def decide_ref(layers: Sequence[LayerCost], envs: dec.EnvArrays,
               efficiency: float = DEFAULT_EFFICIENCY, *,
               cost=None) -> dec.DecisionPlan:
    """Host ``decide_all`` — the semantics the Pallas path must reproduce
    within f32-argmin tolerance."""
    return dec.decide_all(layers, envs, efficiency, cost=cost,
                          backend="numpy")

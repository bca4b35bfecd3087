"""Core S²C² coded-computing library (the paper's contribution).

Public surface:

* :mod:`repro.core.coding` — MDS generator/encode/decode algebra.
* :mod:`repro.core.s2c2` — basic & general S²C² allocation (Algorithm 1).
* :mod:`repro.core.predictor` — LSTM speed forecaster + baselines.
* :mod:`repro.core.traces` — speed-trace generative model (paper §3.2).
* :mod:`repro.core.simulation` — trace-driven latency simulator.
* :mod:`repro.core.strategies` — uncoded/MDS/over-decomp/S²C² strategies.
* :mod:`repro.core.polynomial` — polynomial codes + S²C² on top (§5).
* :mod:`repro.core.gradient_coding` — DP-level gradient coding (beyond-linear).
"""

from repro.core.coding import MDSCode, make_generator
from repro.core.s2c2 import (Allocation, basic_allocation, general_allocation,
                             general_allocation_jax)

__all__ = [
    "MDSCode", "make_generator", "Allocation",
    "basic_allocation", "general_allocation", "general_allocation_jax",
]

"""Quantized / approximate neural-network layers.

``repro.nn.substrate`` holds the ProductSubstrate registry — the single
dispatch point for every scalar-product execution mode (exact, int8,
approx_bitexact, approx_lut, approx_stat, approx_pallas).
``repro.nn.plan`` maps contraction *sites* to substrate specs
(:class:`~repro.nn.plan.SubstratePlan`) — per-layer mixed-substrate
assignments over the same registry.
"""
from repro.nn import conv, plan, quant, substrate  # noqa: F401
from repro.nn.plan import SubstratePlan, as_plan  # noqa: F401
from repro.nn.substrate import (  # noqa: F401
    ContractionSpec,
    Partitioning,
    ProductSubstrate,
    QuantPolicy,
    SubstrateMeta,
    get_substrate,
    list_substrates,
)

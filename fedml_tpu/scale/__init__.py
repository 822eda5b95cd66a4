"""Planet-scale population plane (ROADMAP item 2, host-memory half).

The simulator and cross-silo server were built around an eagerly
materialized federation: every registered client owns Python objects
(dataset arrays, dict entries) from load time, which caps the
reproduction at cohort-sized *populations*. This package separates the
two scales the paper's "anywhere at any scale" claim actually couples:

- ``registry``: N >= 1M registered clients as columnar NumPy/memmap
  state — a few bytes per client — with O(cohort) sampling and
  on-demand per-client data materialization;
- ``cohort``: a heterogeneity-aware packer that turns a sampled cohort's
  variable-size datasets into pow2 compile-cache buckets (the first real
  consumer of ``core/scheduler.py``);
- ``tree``: a two-tier edge-aggregator tree whose fold rides PR 7's
  order-independent ``StreamingAccumulator`` — bit-identical to flat
  aggregation, asserted in tests/test_planet_scale.py (``TestEdgeTree``);
- ``engine``: the registry-backed round loop the simulator routes to
  when ``client_registry_size`` is set.
"""

from .registry import ClientRegistry
from .cohort import CohortGroup, CohortPlan, pack_cohort
from .tree import EdgeAggregationTree

__all__ = [
    "ClientRegistry",
    "CohortGroup",
    "CohortPlan",
    "pack_cohort",
    "EdgeAggregationTree",
]

#!/usr/bin/env bash
# FedAvg equivalence oracle gate — the reference's CI idea
# (ci/CI-script-fedavg.sh:44-63: full-batch 1-epoch federated ==
# centralized to 3 decimals; hierarchical == flat) expressed as the
# pytest oracles that encode exactly those assertions.
set -e
cd "$(dirname "$0")/.."
export JAX_PLATFORMS=cpu
export XLA_FLAGS="--xla_force_host_platform_device_count=8 ${XLA_FLAGS:-}"
python -m pytest tests/test_fedavg_oracle.py tests/test_hier_decentralized.py -q "$@"

#!/usr/bin/env python3
"""``calibrate_lm.py`` with the plants of ``reference/fedavg_twotower.py``.

    python benchmark/tools/calibrate_twotower.py --workload fedavg_twotower_t8192 --seeds 1,2

That tool's ``PLANTS`` name the Mellum2 reference's faults; this one
hands it the control, the half batch and this reference's own (the
carried state dropped at every chunk's start, ``D x`` left out, the
gated norm over all channels, the scaling factor dropped, the shared
expert left out, ``relu`` for ``relu ** 2``) and runs its ``main``. Not
part of a benchmark run.
"""
from __future__ import annotations

import sys

import calibrate_lm

FAULTS = ("state_reset", "no_d_skip", "norm_all_channels", "no_scaling", "no_shared", "relu")
PLANTS = {
    "control_fp8": calibrate_lm.PLANTS["control_fp8"],
    "fault_half_batch": calibrate_lm.PLANTS["fault_half_batch"],
    **{"fault_" + name: (lambda controls, name=name: {"fault": name}) for name in FAULTS},
}

if __name__ == "__main__":
    calibrate_lm.PLANTS.clear()
    calibrate_lm.PLANTS.update(PLANTS)
    sys.exit(calibrate_lm.main())

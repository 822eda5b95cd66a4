#!/usr/bin/env python3
"""``calibrate_lm.py`` with the plants of ``reference/fedavg_trinity.py``.

    python benchmark/tools/calibrate_trinity.py --workload fedavg_trinity_mini_t8192 --seeds 1,2

That tool's ``PLANTS`` name the Mellum2 reference's faults; this one
hands it the control, the half batch and this reference's own (the
attention output ungated, the sandwich norms left out, the embedding
multiplier dropped, the full layers rotated, the window ignored, the
expert bias ignored, the route scale dropped, the shared expert left
out) and runs its ``main``. Not part of a benchmark run.
"""
from __future__ import annotations

import sys

import calibrate_lm

FAULTS = ("no_gate", "no_post_norms", "no_mup", "rotate_full", "no_window", "no_bias",
          "no_route_scale", "no_shared")
PLANTS = {
    "control_fp8": calibrate_lm.PLANTS["control_fp8"],
    "fault_half_batch": calibrate_lm.PLANTS["fault_half_batch"],
    **{"fault_" + name: (lambda controls, name=name: {"fault": name}) for name in FAULTS},
}

if __name__ == "__main__":
    calibrate_lm.PLANTS.clear()
    calibrate_lm.PLANTS.update(PLANTS)
    sys.exit(calibrate_lm.main())

#!/usr/bin/env python3
"""``calibrate_lm.py`` with the plants of ``reference/fedavg_lfm2.py``.

    python benchmark/tools/calibrate_lfm2.py --workload fedavg_lfm2_t4096 --seeds 1,2

That tool's ``PLANTS`` name the Mellum2 reference's faults; this one
hands it the control, the half batch and this reference's own (the
selection bias ignored, the convolution reading one token ahead, the C
gate left out, the renormalisation dropped, the dense layer at an
expert's width) and runs its ``main``. Not part of a benchmark run.
"""
from __future__ import annotations

import sys

import calibrate_lm

PLANTS = {
    "control_fp8": calibrate_lm.PLANTS["control_fp8"],
    "fault_half_batch": calibrate_lm.PLANTS["fault_half_batch"],
    **{"fault_" + name: (lambda controls, name=name: {"fault": name})
       for name in ("no_bias", "acausal_conv", "no_c_gate", "no_renorm", "dense_width")},
}

if __name__ == "__main__":
    calibrate_lm.PLANTS.clear()
    calibrate_lm.PLANTS.update(PLANTS)
    sys.exit(calibrate_lm.main())

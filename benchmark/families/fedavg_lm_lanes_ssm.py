"""Family driver: ``fedavg_lm_lanes``', for a model with state-space
sublayers.

``families/fedavg_lm_lanes.py``'s driver unchanged in its set-up (the
refusal of a program that cannot build the model, and of another round
executable, among it), its drive, its window's clock, its comparison
and its end-to-end metrics. One thing is added: ``ssm_chunks`` -- the
chunks the program's state-space scans ran, a counter of the round's
record -- joins the counters summed over the window's reported rounds,
for ``ssm_scan_roofline``. That family's list of counters is closed, so
the sum is taken here; nothing is compared against it.
"""

from __future__ import annotations

from typing import Any, Dict

import fedavg_lm_lanes

COUNTER = "ssm_chunks"


class Driver(fedavg_lm_lanes.Driver):
    def window(self, seconds: float) -> Dict[str, Any]:
        api, n_hist = self.api, len(self.api.history)
        win = super().window(seconds)
        hist = api.history[n_hist:]
        if hist and all(COUNTER in h for h in hist):
            win["counters"][COUNTER] = float(sum(h[COUNTER] for h in hist))
        return win

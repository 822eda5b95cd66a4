"""Family driver: ``fedavg_lm``'s, for a federation whose cohort the
round engine runs one lane after another.

``families/fedavg_lm.py``'s driver unchanged in its drive, its window's
clock, its comparison and its end-to-end metrics. Three things are
added, for a configuration whose silos are of unequal length and whose
expert layers carry a selection bias:

- set-up refuses, before any data is made, a program that cannot build
  the configuration's model (the parent of the PR that added the layer
  kinds: seconds, not the minutes the data and the seed's weights
  take), and refuses after the program is built one that did not take
  the lane-after-lane round executable (``FedAvgAPI._round_exec_name``):
  a later change of the engine's rule cannot silently move the cell
  onto another executable;
- ``moe_bias_moved`` joins the counters summed over the window's
  reported rounds (nothing is compared against it);
- the reported rounds' ``steps_run`` / ``steps_packed`` are summed
  (``lane_steps``), and the sequence slots the window's rounds really
  computed are counted: ``slot_samples`` is the static ``bucket x
  num_batches x batch`` a round, of which the lanes' step loops ran the
  share the program's pipeline reports for a call
  (``lane_steps_run_share``; every call runs the same cohorts).
"""

from __future__ import annotations

from typing import Any, Dict

import fedavg_lm
from harness import BenchError

COUNTERS = fedavg_lm.COUNTERS + ("moe_bias_moved",)
LANES = "simulation.round_fn_ragged"


class Driver(fedavg_lm.Driver):
    def setup(self) -> None:
        import jax

        from fedml_tpu import models

        try:
            model = models.create(self._args(), int(self.model["vocab_size"]))
            jax.eval_shape(model.init, jax.random.PRNGKey(0))
        except Exception as e:  # whatever an older program trips over first (the parent: a KeyError)
            raise BenchError(f"the program cannot build this configuration's model: {type(e).__name__}: {e}")
        super().setup()
        took = self.api._round_exec_name()
        if took != LANES:
            raise BenchError(f"the round executable is {took}, not the lane-after-lane {LANES}")

    def window(self, seconds: float) -> Dict[str, Any]:
        api, n_hist = self.api, len(self.api.history)
        win = super().window(seconds)
        hist = api.history[n_hist:]
        total = lambda k: float(sum(h[k] for h in hist))
        win["counters"] = {k: total(k) for k in COUNTERS if hist and all(k in h for h in hist)}
        win["lane_steps"] = {k: total(k) for k in ("steps_run", "steps_packed") if all(k in h for h in hist)}
        share = api.pipeline_stats.get("lane_steps_run_share")
        if share is not None:
            win["train_slot_samples"] = float(share) * win["slot_samples"]
        return win

"""Family driver: ``fedavg_lm_lanes``', for a decoder whose attention
output is gated and whose sublayers carry sandwich norms.

``families/fedavg_lm_lanes.py``'s driver unchanged in its drive, its
window's clock, its comparison and its end-to-end metrics. One thing is
added to its set-up: before any data is made, the program's model for
this configuration is built from shapes alone and refused unless its
parameter tree is the reference's (``param_shapes``: the gate's
projection and both post-norms in every layer among it) and the module
carries the configuration's embedding multiplier. A program that does
not know these fields (the parent of the PR that added them) takes
their arguments without a word and would build a model without them; it
is refused here in seconds, not after the minutes the data and the
seed's weights take.
"""

from __future__ import annotations

import fedavg_lm_lanes
from harness import BenchError


def _shapes(tree, is_leaf=None):
    """``{path: shape}`` of a tree of arrays or of shape tuples."""
    import jax

    return {jax.tree_util.keystr(path): tuple(getattr(leaf, "shape", leaf))
            for path, leaf in jax.tree_util.tree_leaves_with_path(tree, is_leaf=is_leaf)}


class Driver(fedavg_lm_lanes.Driver):
    def setup(self) -> None:
        import jax

        from fedml_tpu import models

        model = models.create(self._args(), int(self.model["vocab_size"]))
        have = _shapes(jax.eval_shape(model.init, jax.random.PRNGKey(0)))
        want = _shapes(self.ref.param_shapes(self.model), is_leaf=lambda x: isinstance(x, tuple))
        if have != want:
            missing = sorted(set(want) - set(have))[:4]
            raise BenchError(
                f"the program cannot build this configuration's model: its parameter tree "
                f"lacks {missing} or differs from the reference's in shape")
        multiplier = getattr(model.module, "embedding_multiplier", None)
        if multiplier != float(self.model["embedding_multiplier"]):
            raise BenchError(
                f"the program cannot build this configuration's model: embedding multiplier "
                f"{multiplier}, not {self.model['embedding_multiplier']}")
        super().setup()

from _common import module_ms_per_call


def read(ctx):
    """Device milliseconds of the evaluation executable, per evaluation
    (its calls over the training and the held-out split together)."""
    ms = module_ms_per_call(ctx, "jit_eval_all")
    return None if ms is None else ms * 2.0

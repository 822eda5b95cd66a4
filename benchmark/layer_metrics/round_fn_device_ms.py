from _common import module_ms_per_call


def read(ctx):
    """Device milliseconds of the round executable, per round."""
    return module_ms_per_call(ctx, "jit_round_fn")

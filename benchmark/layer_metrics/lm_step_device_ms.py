from _common import module_ms_per_call


def read(ctx):
    """Device milliseconds of the epoch executable, per optimizer step."""
    steps = float(ctx["cell"].traffic["steps_per_epoch"])
    return module_ms_per_call(ctx, "jit_epoch", per=steps)

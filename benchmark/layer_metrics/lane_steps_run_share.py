def read(ctx):
    """Lane-steps the cohort's step loops ran over the lane-steps its
    lanes were packed to, summed over the window's reported rounds (the
    round executable's own ``steps_run`` / ``steps_packed``): 1.0 would
    mean the engine stepped the batches that hold no sample."""
    steps = ctx["window"].get("lane_steps") or {}
    if not steps.get("steps_packed"):
        return None
    return steps["steps_run"] / steps["steps_packed"]

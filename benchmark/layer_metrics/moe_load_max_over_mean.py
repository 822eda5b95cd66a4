def read(ctx):
    """The fullest held expert's tokens over the mean held expert's,
    summed over the layers, steps and lanes of the window's reported
    rounds (the program's own counters): 1.0 is an even load."""
    c = ctx["window"].get("counters") or {}
    if not c.get("moe_expert_tokens_mean"):
        return None
    return c["moe_expert_tokens_max"] / c["moe_expert_tokens_mean"]

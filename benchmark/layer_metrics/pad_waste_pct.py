def read(ctx):
    """Share of the sample slots a round computes that hold no useful
    sample: masked slots of real clients and whole padded lanes."""
    win = ctx["window"]
    if not win.get("slot_samples"):
        return None
    return 100.0 * (1.0 - win["useful_samples"] / win["slot_samples"])

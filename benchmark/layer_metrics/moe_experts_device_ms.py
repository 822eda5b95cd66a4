from _lm_scopes import ms_per_round


def read(ctx):
    """Device milliseconds a round inside ``moe.experts``: the grouped
    gated-SiLU product over the held experts, forward and backward."""
    return ms_per_round(ctx, "moe.experts")

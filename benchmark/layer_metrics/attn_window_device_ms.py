from _lm_scopes import ms_per_round


def read(ctx):
    """Device milliseconds a round inside ``blk.attn.window``: the
    sliding layers' norm, projections, rotation, windowed flash kernel
    and its backward scan."""
    return ms_per_round(ctx, "blk.attn.window")

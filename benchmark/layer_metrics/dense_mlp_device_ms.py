from _lane_scopes import ms_per_round


def read(ctx):
    """Device milliseconds a round inside ``blk.mlp.dense``: the leading
    layer's dense gated-SiLU MLP, forward, rematerialised and backward."""
    return ms_per_round(ctx, "blk.mlp.dense")

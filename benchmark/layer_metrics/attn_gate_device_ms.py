from _gated_scopes import ms_per_round


def read(ctx):
    """Device milliseconds a round inside ``blk.attn.gate``: the
    attention output gate's projection, sigmoid and product, forward,
    rematerialised and backward."""
    return ms_per_round(ctx, "blk.attn.gate")

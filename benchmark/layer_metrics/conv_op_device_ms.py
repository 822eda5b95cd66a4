from _lane_scopes import ms_per_round


def read(ctx):
    """Device milliseconds a round inside ``blk.conv``: the gated short
    convolution's norm, ``in_proj``, gates, taps and ``out_proj``,
    forward, rematerialised and backward."""
    return ms_per_round(ctx, "blk.conv")

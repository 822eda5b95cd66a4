from _ssm_scopes import ms_per_round


def read(ctx):
    """Device milliseconds a round inside ``blk.ssm``: the Mamba-2
    sublayers' norm, ``in_proj``, convolution, scan, gated norm and
    ``out_proj``, forward, rematerialised and backward."""
    return ms_per_round(ctx, "blk.ssm")

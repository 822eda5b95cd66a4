from _ssm_scopes import ms_per_round


def read(ctx):
    """Device milliseconds a round inside ``blk.ssm.scan``: everything
    ``ops/ssd.py`` does (the chunks' masked products, their states, the
    recurrence between chunks), forward, rematerialised and backward."""
    return ms_per_round(ctx, "blk.ssm.scan")

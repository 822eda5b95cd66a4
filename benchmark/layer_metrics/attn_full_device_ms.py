from _lm_scopes import ms_per_round


def read(ctx):
    """Device milliseconds a round inside ``blk.attn.full``: the full
    (causal, YaRN) layers' attention, forward, rematerialised and
    backward."""
    return ms_per_round(ctx, "blk.attn.full")

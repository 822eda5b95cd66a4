from _gated_scopes import ms_per_round


def read(ctx):
    """Device milliseconds a round inside ``blk.post_norm``: what stays
    apart of the sandwich norms on the attention's and the
    feed-forward's outputs, forward, rematerialised and backward. XLA
    fuses most of a norm's work into the add or product beside it, and
    such a fusion carries that neighbour's scope: this reads the unfused
    remainder, not what the norms cost."""
    return ms_per_round(ctx, "blk.post_norm")

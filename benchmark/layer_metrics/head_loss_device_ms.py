from _lm_scopes import ms_per_round


def read(ctx):
    """Device milliseconds a round inside ``lm.head_loss``: the final
    norm, the output head over the vocabulary slice and the float32
    cross-entropy, forward and backward."""
    return ms_per_round(ctx, "lm.head_loss")

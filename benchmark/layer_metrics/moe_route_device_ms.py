from _lm_scopes import ms_per_round


def read(ctx):
    """Device milliseconds a round of the expert layer outside its
    grouped product: ``moe.route`` (router, top-k, sort, the gather of
    the rows) and ``moe.combine`` (the weighted scatter back onto the
    tokens), forward and backward."""
    return ms_per_round(ctx, "moe.route", "moe.combine")

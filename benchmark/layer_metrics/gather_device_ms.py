from _scopes import scope_ms_per_round


def read(ctx):
    """Device milliseconds a round inside the scope ``fed.gather``: the
    cohort's gather from the packed federation and its validity mask."""
    return scope_ms_per_round(ctx, "fed.gather")

from _scopes import scope_ms_per_round


def read(ctx):
    """Device milliseconds a round inside the scope ``fed.aggregate``:
    weight normalization, the weighted reduction, the metric sums."""
    return scope_ms_per_round(ctx, "fed.aggregate")

from _scopes import scope_ms_per_round


def read(ctx):
    """Device milliseconds a round inside the scope ``fed.local_train``:
    the vmapped local-training scan."""
    return scope_ms_per_round(ctx, "fed.local_train")

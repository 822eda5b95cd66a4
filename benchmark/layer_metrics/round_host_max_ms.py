from _scopes import round_host


def read(ctx):
    """The largest of what ``round_host_ms`` averages: the window's
    worst round on the host, the stall reading."""
    per_round = round_host(ctx)
    return None if per_round is None else max(per_round)

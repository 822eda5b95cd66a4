from _scopes import round_host


def read(ctx):
    """Mean over the window's rounds of the ``round`` span less the
    ``round.wait`` and ``flush.fetch`` inside it: the host's own work a
    round, milliseconds."""
    per_round = round_host(ctx)
    return None if per_round is None else sum(per_round) / len(per_round)

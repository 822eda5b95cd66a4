from _ssm_scopes import ms_per_round


def read(ctx):
    """Device milliseconds a round inside ``moe.shared``: the shared
    expert's two products on every token, forward, rematerialised and
    backward."""
    return ms_per_round(ctx, "moe.shared")

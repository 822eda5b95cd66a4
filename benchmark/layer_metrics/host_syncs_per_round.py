def read(ctx):
    """Device-to-host fetches a round, as the round pipeline counts them."""
    return ctx["window"].get("host_syncs_per_round")

def read(ctx):
    """Seconds of the harness span around the program's ``data.load()``."""
    return ctx["facts"]["spans"].get("data_setup_s")

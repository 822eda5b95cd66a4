from _lm_scopes import flash_fwd_roofline


def read(ctx):
    """The windowed flash forward kernel's share of its roofline."""
    return flash_fwd_roofline(
        ctx, "flash_attention_window_fwd", lambda model: model["sliding_window"])

from _lm_scopes import flash_fwd_roofline


def read(ctx):
    """The full (causal) flash forward kernel's share of its roofline."""
    return flash_fwd_roofline(ctx, "flash_attention_fwd", lambda model: None)

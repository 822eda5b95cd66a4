from _common import mfu_pct as read  # noqa: F401  whole-window required operations over chips x peak

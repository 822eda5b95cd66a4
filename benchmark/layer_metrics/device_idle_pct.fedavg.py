from _common import device_idle_pct as read  # noqa: F401  1 - busy/window of the traced run

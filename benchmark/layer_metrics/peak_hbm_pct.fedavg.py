from _common import peak_hbm_pct as read  # noqa: F401  memory_stats() peak of the fullest chip over its HBM

def read(ctx):
    """Executables this process had to compile (persistent cache misses,
    from the program's own listener); 0 once the cache is warm."""
    return ctx["facts"].get("counters", {}).get("compile_cache_misses")

def read(ctx):
    """Share of the token-choices the expert layers' selection bias
    changed against the unbiased top-k, over the window's reported
    rounds: the program's ``moe_bias_moved`` over every choice those
    rounds' steps made (steps run x batch x tokens x top-k x sparse
    layers; a masked sequence slot of a step that ran routes too)."""
    win, cfg = ctx["window"], ctx["cell"].config
    moved = (win.get("counters") or {}).get("moe_bias_moved")
    steps = (win.get("lane_steps") or {}).get("steps_run")
    model = cfg.get("model", {})
    if moved is None or not steps or "num_dense_layers" not in model:
        return None
    sparse = len(model["layer_types"]) - model["num_dense_layers"]
    choices = (steps * cfg["federation"]["batch_size"] * model["seq_len"]
               * model["num_experts_per_tok"] * sparse)
    return moved / choices

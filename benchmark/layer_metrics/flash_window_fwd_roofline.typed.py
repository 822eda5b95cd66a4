def read(ctx):
    """The windowed flash forward kernel's share of its roofline, as
    ``_lm_scopes.flash_fwd_roofline`` reckons it, with two counts taken
    otherwise, as ``flash_full_fwd_roofline.typed`` takes them: the
    layers the kernel runs in are those whose type *is*
    ``sliding_attention``, and the training sequences pushed through it
    are the ones the lanes' step loops really ran
    (``train_slot_samples``), twice under remat, plus the evaluations'
    slots."""
    k = ctx["trace"]["kernels"].get("flash_attention_window_fwd")
    cfg, win = ctx["cell"].config, ctx["window"]
    model = cfg.get("model", {})
    if not k or not k["count"] or k["total_s"] <= 0 or "train_slot_samples" not in win:
        return None
    layers = sum(1 for kind in model.get("layer_types", ()) if kind == "sliding_attention")
    passes = 2.0 if cfg.get("program_args", {}).get("remat") else 1.0
    sequences = passes * win["train_slot_samples"] + win.get("eval_slot_samples", 0.0)
    need = ctx["flops"].flash_fwd_sequence(model, model["sliding_window"])
    peaks = ctx["peaks"]
    least = max(need["flops"] / peaks["bf16_flops_per_s"], need["bytes"] / peaks["hbm_bytes_per_s"])
    return 100.0 * least * sequences * layers / k["total_s"]

def read(ctx):
    """The flash forward kernel's share of its roofline: the least time
    the chip could take for one call (the larger of operations over peak
    FLOP/s and bytes over peak bytes/s, both from shapes) over the
    kernel's mean device time in the trace."""
    cell, peaks = ctx["cell"], ctx["peaks"]
    k = ctx["trace"]["kernels"].get("flash_attention_fwd")
    if not k or not k["count"]:
        return None
    tr = cell.config["training"]
    need = ctx["flops"].flash_fwd_call(cell.config["model"], tr["batch_size"], tr["seq_len"])
    least = max(need["flops"] / peaks["bf16_flops_per_s"], need["bytes"] / peaks["hbm_bytes_per_s"])
    return 100.0 * least / (k["total_s"] / k["count"])

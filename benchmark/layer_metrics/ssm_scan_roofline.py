from _ssm_scopes import seconds_in_window

# a trained chunk in forward passes: the forward, and the backward at
# twice a forward; under remat the forward runs a second time
PASSES = {True: 4.0, False: 3.0}


def read(ctx):
    """The state-space scan's share of its roofline over the traced
    window: the least time the chip could take for every chunk pushed
    through it (the larger of operations over peak FLOP/s and bytes over
    peak bytes/s, both a chunk from shapes: ``flops.ssd_chunk``) over
    the whole device time of ``blk.ssm.scan``, in the round executable
    and the evaluation's. The chunks a sequence costs are the program's
    own count -- ``ssm_chunks`` over the sequences of the reported
    rounds' steps (layers x T / chunk) -- times the sequences the lanes'
    step loops really ran in the window (``PASSES`` forward passes each)
    and the evaluations' slots (one)."""
    win, cfg = ctx["window"], ctx["cell"].config
    chunks = (win.get("counters") or {}).get("ssm_chunks")
    steps = (win.get("lane_steps") or {}).get("steps_run")
    if not chunks or not steps or "train_slot_samples" not in win or not hasattr(ctx["flops"], "ssd_chunk"):
        return None
    seconds = seconds_in_window(ctx, "blk.ssm.scan")
    if not seconds:
        return None
    a_sequence = chunks / (steps * cfg["federation"]["batch_size"])
    passes = PASSES[bool(cfg.get("program_args", {}).get("remat"))]
    pushed = a_sequence * (passes * win["train_slot_samples"] + win.get("eval_slot_samples", 0.0))
    need, peaks = ctx["flops"].ssd_chunk(cfg["model"]), ctx["peaks"]
    least = max(need["flops"] / peaks["bf16_flops_per_s"], need["bytes"] / peaks["hbm_bytes_per_s"])
    return 100.0 * least * pushed / seconds

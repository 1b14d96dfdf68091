from benchmark.readers import train_tokens_per_s_chip


def read(run):
    """Model FLOPs a token (no recomputation) times tokens per second
    per chip, over the chip's published peak."""
    rate = train_tokens_per_s_chip.read(run)
    if rate is None or run.peaks is None:
        return None
    w = run.window
    flops = run.family.train_flops_per_token(w["program_config"],
                                             w["sequence_tokens"])
    return 100.0 * flops * rate / run.peaks["bf16_flops_per_s"]

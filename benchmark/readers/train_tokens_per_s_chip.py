def read(run):
    """Tokens of the optimizer steps completed inside the window, over
    the window (it ends when the last loss is ready) and the chips."""
    w = run.window
    if w["kind"] != "train":
        return None
    return (w["steps"] * w["tokens_per_step"]
            / (w["t_end"] - w["t_open"]) / run.chips)

"""Arithmetic on a serving window that several readers share."""


def seconds(run):
    return run.window["t_end"] - run.window["t_open"]


def steps(run):
    """The rows of the steps that began inside the window."""
    w = run.window
    return [s for s in w["ledger"].steps[w["first_step"]:]
            if s[0] >= w["t_open"]]


def gaps(run):
    """Every gap between consecutive output tokens of one request whose
    two tokens both fell inside the window, in seconds."""
    w = run.window
    out = []
    for st in w["ledger"].stamps.values():
        out.extend(b - a for a, b in zip(st, st[1:])
                   if a >= w["t_open"] and b <= w["t_end"])
    return out


def first_token_waits(run):
    """Due time to first token, for requests due inside the window whose
    first token came inside it."""
    w = run.window
    led = w["ledger"]
    return [st[0] - led.due_at[rid] for rid, st in led.stamps.items()
            if st and led.due_at[rid] >= w["t_open"] and st[0] <= w["t_end"]]


def decode_only(run):
    """Steps that admitted nothing and absorbed no prompt chunk, with at
    least one slot decoding."""
    return [s for s in steps(run) if s[2] == 0 and s[3] == 0 and s[4] > 0]

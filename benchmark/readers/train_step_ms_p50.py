from benchmark.harness import trace
from benchmark.harness.clock import percentile


def read(run):
    """Device time of one run of the step program (the program with
    most time in the trace), median over the traced steps."""
    if run.traced is None or run.window["kind"] != "train":
        return None
    progs = trace.program_seconds(run.traced)
    if not progs:
        return None
    runs = max(progs.values(), key=sum)
    return 1e3 * percentile(runs, 50)

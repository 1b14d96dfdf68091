"""Requests due on a schedule, whatever the engine does."""

from benchmark.harness import serve


def run(run):
    return serve.run_serving(run, backlog=False)

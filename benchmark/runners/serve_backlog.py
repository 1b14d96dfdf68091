"""A standing queue: what any offered rate above the knee becomes."""

from benchmark.harness import serve


def run(run):
    return serve.run_serving(run, backlog=True)

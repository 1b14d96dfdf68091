"""Optimizer steps on a seeded corpus, cycled."""

from benchmark.harness import train


def run(run):
    return train.run_training(run)

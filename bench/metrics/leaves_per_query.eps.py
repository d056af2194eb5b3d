"""Refinement loop: leaves visited (``QueryResult.leaves_visited``,
summed over the window's engine calls) per query answered, in the
epsilon cell."""

from bench.readers import total


def read(run):
    answered = len(run.answered)
    return total(run, "leaves_visited") / answered if answered else None

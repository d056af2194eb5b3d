"""Serving front: mean lane batch over ``max_batch``, from the
``serve.lane.batch_size`` histogram's growth over the window."""


def read(run):
    if not run.batches:
        return None
    return run.batched / run.batches / run.max_batch

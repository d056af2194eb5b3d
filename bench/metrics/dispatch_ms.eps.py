"""Engine: median over the window's engine calls of the host ms inside
the resident engine's eager ``shard_map`` call (``QueryResult.
dispatch_s``, the ``engine.dispatch`` span), in the epsilon cell."""

import statistics


def read(run):
    ds = [c.result.dispatch_s for c in run.calls
          if hasattr(c.result, "dispatch_s")]
    return 1e3 * statistics.median(ds) if ds else None

"""Storage: bytes read from the store (``OocStats.bytes_read``, demand
and prefetch) per query answered, in MiB."""


def read(run):
    st = run.stats()
    answered = len(run.answered)
    if not st or not answered:
        return None
    return sum(s.bytes_read for s in st) / answered / 2**20

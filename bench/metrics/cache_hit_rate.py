"""Storage: leaf-cache hits over lookups (``OocStats`` hits and misses,
summed over the window's engine calls)."""


def read(run):
    st = run.stats()
    looked = sum(s.hits + s.misses for s in st)
    return sum(s.hits for s in st) / looked if looked else None

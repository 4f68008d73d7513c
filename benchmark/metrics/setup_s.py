"""Process start (the first process, before it re-executes itself) to the
first timed operation: imports, native builds, chain or net built from
the seed, warm pass, compiles."""

LAYER = "end to end"
MOVES = None


def read(r):
    return r.setup_s

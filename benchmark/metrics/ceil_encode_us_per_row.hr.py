"""ceil_encode_us_per_row.hr: wire.encode.ceil stage seconds (the native
re-encode of the rows over the floor caps at the ceiling caps) over the
rows driven, in microseconds (srv/evaluator.py); None where the program
has no such stage or no row overflowed."""


def read(r):
    return r.per_row(r.stage_seconds("wire.encode.ceil"), 1e6)

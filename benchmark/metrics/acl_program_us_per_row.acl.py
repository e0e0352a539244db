"""acl_program_us_per_row.acl: device seconds inside the ``acs_dense_acl``
module events of the traced window (the ``XLA Modules`` line,
benchmark/roofline.py) over the rows driven, in microseconds; None where
the trace holds no such event."""

import harness
import roofline


def read(r):
    found = roofline.module_time(harness.TRACE_DIR, "acs_dense_acl")
    return r.per_row(found[1], 1e6) if found else None

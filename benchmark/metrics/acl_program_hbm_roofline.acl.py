"""acl_program_hbm_roofline.acl: the ``acs_dense_acl`` program's HBM bytes
a call over its device seconds a call, as a share of the chip's HBM peak,
in percent.  The bytes of each call come from its dispatched shape (the
path counters ``acl-program@<shape>``, srv/evaluator.py) by
``roofline.acl_program_bytes``; the seconds from the program's module
events in the traced window.  None where the program has no such counter,
the trace no such event, or the run no TPU."""

import harness
import roofline


def read(r):
    shapes = {key: n for key, n in r.counters["paths"].items()
              if key.startswith(roofline.SHAPE_KEY)}
    found = roofline.module_time(harness.TRACE_DIR, "acs_dense_acl")
    peak = roofline.hbm_peak()
    if not shapes or not found or not peak:
        return None
    calls, seconds = found
    moved = sum(n * roofline.acl_program_bytes(roofline.parse_shape(key))
                for key, n in shapes.items())
    per_call = moved / sum(shapes.values())
    return 100.0 * per_call / (seconds / calls) / peak

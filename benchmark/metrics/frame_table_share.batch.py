"""frame_table_share.batch: the native-wire rows whose reply frame came
from the frame table (path counter ``wire-frame-table``) over those and the
rows assembled per row (``wire-frame-pb``), in percent
(srv/transport_grpc.py); None where neither counter fired."""


def read(r):
    paths = r.counters["paths"]
    table = paths.get("wire-frame-table", 0)
    total = table + paths.get("wire-frame-pb", 0)
    return 100.0 * table / total if total else None

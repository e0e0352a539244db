"""ceil_share.hr: the native-wire rows served by the over-cap re-encode
(path counter ``native-wire-ceil``) over all native-wire rows served
(``native-wire``), in percent (srv/evaluator.py); None where no row was
served natively.  Read only in the traced run, as every per-layer
metric."""


def read(r):
    paths = r.counters["paths"]
    total = paths.get("native-wire", 0)
    return 100.0 * paths.get("native-wire-ceil", 0) / total if total else None

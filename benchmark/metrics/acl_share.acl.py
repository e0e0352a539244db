"""acl_share.acl: the native-wire rows served that carried ACL pairs to
the kernel's verifyACL pass (path counter ``native-wire-acl``) over all
native-wire rows served (``native-wire``), in percent
(srv/evaluator.py); None where the program has no such counter or no row
was served natively."""


def read(r):
    paths = r.counters["paths"]
    total, acl = paths.get("native-wire", 0), paths.get("native-wire-acl")
    return 100.0 * acl / total if total and acl is not None else None

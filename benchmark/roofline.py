"""The device time of one program and the HBM bytes it moves, for a
program's share of its roofline.

- ``module_time(trace_dir, program)``: the events of the ``XLA Modules``
  line of every ``/device:TPU:<n>`` plane named after the jitted
  ``program`` (``jit_<program>(<fingerprint>)``) that overlap the
  harness's ``bench.window``: how many, and their seconds.  None where the
  trace has no such event (a CPU run, or a program without it).
- ``acl_program_bytes(shape)``: the HBM bytes of one ``acs_dense_acl``
  call at a dispatched shape, as the program's path counter
  ``acl-program@<shape>`` names it (``parse_shape``): its request operands
  and its results at those caps, plus the verifyACL scan's carries,
  written and read back at every step.  Left out: the policy tables
  (constants of the program under 1 MB), the regex matrices (``[W, E]``
  booleans, a few KB a call) and the condition arrays (empty on trees
  without conditions).
- ``HBM_PEAK``: bytes per second by ``device_kind`` (the published peak
  of one TPU v5e chip, Google Cloud's "TPU v5e" page: 819 GB/s).

The program's work is boolean algebra on the VPU with no matrix products
that grow with the ACL pass, so it has no FLOP share: only bytes.
"""

from __future__ import annotations

import glob
import os
import re

import trace_reduce

HBM_PEAK = {"TPU v5 lite": 819e9}

INT32, BOOL = 4, 1
# the request operands of a dispatch: name -> (bytes an element, the caps
# whose product is its width a row; () for one element a row)
OPERANDS = {
    "r_acl_ent": (INT32, ("NACLE",)),
    "r_acl_hr": (INT32, ("NHR", 2)),
    "r_acl_inst": (INT32, ("NACLE", "NACLI")),
    "r_acl_short": (INT32, ()),
    "r_act_ids": (INT32, ("NACT",)),
    "r_act_vals": (INT32, ("NACT",)),
    "r_ctx_present": (BOOL, ()),
    "r_ent_e": (INT32, ("NR",)),
    "r_ent_valid": (BOOL, ("NR",)),
    "r_ent_vals": (INT32, ("NR",)),
    "r_has_props": (BOOL, ()),
    "r_has_target": (BOOL, ()),
    "r_hr": (INT32, ("NHR", 2)),
    "r_hr_roles": (INT32, ("NHRR",)),
    "r_inst_has_owners": (BOOL, ("NI",)),
    "r_inst_id": (INT32, ("NI",)),
    "r_inst_owner_ent": (INT32, ("NI", "NOWN")),
    "r_inst_owner_inst": (INT32, ("NI", "NOWN")),
    "r_inst_present": (BOOL, ("NI",)),
    "r_inst_run": (INT32, ("NI",)),
    "r_inst_valid": (BOOL, ("NI",)),
    "r_n_entity_attrs": (INT32, ()),
    "r_n_ra": (INT32, ()),
    "r_op_has_owners": (BOOL, ("NOP",)),
    "r_op_owner_ent": (INT32, ("NOP", "NOWN")),
    "r_op_owner_inst": (INT32, ("NOP", "NOWN")),
    "r_op_present": (BOOL, ("NOP",)),
    "r_op_vals": (INT32, ("NOP",)),
    "r_own_bits": (INT32, ("OWN",)),
    "r_own_runs": (INT32, ("OWN",)),
    "r_prop_run": (INT32, ("NP",)),
    "r_prop_sfx": (INT32, ("NP",)),
    "r_prop_tail": (INT32, ("NP",)),
    "r_prop_vals": (INT32, ("NP",)),
    "r_ra2": (INT32, ("NRA", 2)),
    "r_ra3": (INT32, ("NRA", 3)),
    "r_rel_bits": (INT32, ("REL",)),
    "r_rel_runs": (INT32, ("REL",)),
    "r_roles": (INT32, ("NROLE",)),
    "r_sub_ids": (INT32, ("NSUB",)),
    "r_sub_vals": (INT32, ("NSUB",)),
    "r_subject_id": (INT32, ()),
}
# decision, cacheable, status
RESULT_BYTES = INT32 + BOOL + INT32
SHAPE_KEY = "acl-program@"
FINGERPRINT = re.compile(r"\(\d+\)$")     # jit_<name>(<fingerprint>)


def parse_shape(key: str) -> dict:
    """``acl-program@b4096.t512.NR4...`` -> {"b": 4096, "t": 512, "NR": 4,
    ...}."""
    return {name: int(n) for name, n in
            re.findall(r"([A-Za-z]+)(\d+)", key.split("@", 1)[1])}


def operand_bytes_a_row(shape: dict) -> int:
    total = 0
    for size, dims in OPERANDS.values():
        n = 1
        for d in dims:
            n *= shape[d] if isinstance(d, str) else d
        total += size * n
    return total


def scan_carry_bytes(shape: dict) -> int:
    """The verifyACL create-path scan (``ops/kernel._acl_pass``): one step
    per (HR role, ACL instance), each reading and writing its carries
    ``validated`` bool [B, T, NACLE, NACLI], ``broken`` bool [B, T, NACLE]
    and ``last_ev`` int32 [B, T, NACLE]."""
    b, t, e, i = shape["b"], shape["t"], shape["NACLE"], shape["NACLI"]
    carry = b * t * e * (i * BOOL + BOOL + INT32)
    return 2 * shape["NHRR"] * i * carry


def acl_program_bytes(shape: dict) -> int:
    """HBM bytes of one ``acs_dense_acl`` call at ``shape``."""
    rows = shape["b"]
    return (rows * (operand_bytes_a_row(shape) + RESULT_BYTES)
            + scan_carry_bytes(shape))


def hbm_peak() -> float | None:
    """The HBM peak of the chip the run holds; None off a TPU.  A TPU
    missing from ``HBM_PEAK`` is an error, not a default."""
    import jax

    device = jax.devices()[0]
    if device.platform != "tpu":
        return None
    return HBM_PEAK[device.device_kind]


_MODULES: dict = {}


def module_time(trace_dir: str, program: str):
    """(events, seconds) of ``program``'s module events in the window of
    the newest trace under ``trace_dir``, or None."""
    paths = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not paths:
        return None
    path = max(paths, key=os.path.getmtime)
    key = (path, os.path.getmtime(path))
    if key not in _MODULES:
        from jax.profiler import ProfileData

        _MODULES.clear()
        _MODULES[key] = _modules(ProfileData.from_file(path))
    modules = _MODULES[key]
    found = modules.get(f"jit_{program}") or modules.get(f"jit({program})")
    return tuple(found) if found else None


def _modules(data) -> dict:
    """jitted name -> [events, seconds] inside the window."""
    events, window = [], None
    for plane in data.planes:
        if trace_reduce.DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == "XLA Modules":
                    events += [(FINGERPRINT.sub("", ev.name), ev.start_ns,
                                ev.duration_ns) for ev in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == trace_reduce.WINDOW_SPAN:
                        window = (ev.start_ns, ev.start_ns + ev.duration_ns)
    out: dict = {}
    for name, start, duration in events:
        if window is None or (start + duration > window[0]
                              and start < window[1]):
            entry = out.setdefault(name, [0, 0.0])
            entry[0] += 1
            entry[1] += duration * 1e-9
    return out

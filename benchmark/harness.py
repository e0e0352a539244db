"""One cell of the benchmark, once: set-up, the measured window, the
comparison with the plain reference, and the result line.

Everything a cell is made of is found by name, so a later cell, mix,
metric or configuration is added as new files and ``BENCHMARK.json``
entries alone:

- the cell: an entry of ``BENCHMARK.json``'s ``workloads``;
- a traffic mix: ``traffic/<name>.json`` (``traffic.py``), whose
  ``rows.kind`` names a row kind;
- a row kind: ``row_kinds/<kind>.py`` with ``rows(n, g, params) -> list``
  of row dicts (``traffic.row``'s shape), drawn from the generator ``g``;
- a metric: ``metrics/<name>.py`` with ``read(reading) -> float | None``;
- a configuration: its file (``BENCHMARK.json``'s ``file``) with
  ``tree``: ``{"seed": "<dir>"}`` or ``{"builder": "<name>", "params":
  {...}}``, a builder being ``tree_builders/<name>.py`` with
  ``build(params, g) -> list`` of nested policy sets (``trees.py``);
  ``reference``: ``<name>``, the plain reference
  ``references/<name>.py`` (``reference.py`` when absent), with
  ``Reference(tree).decide(Row(target, context))``, ``CODES``,
  ``CONTROLS`` and ``Unsupported`` as ``reference.py`` has them;
  ``control``: a key of that module's ``CONTROLS``; ``overlay``: the
  program's configuration keys it sets; and ``small``: the overlay of
  the configuration at the size the benchmark's tests run (a mix's
  ``small`` likewise).

From the program this takes only the system under test (``Worker`` behind
``GrpcServer``, driven through ``GrpcClient``'s channel), its stage
histograms, path counters and compile state.
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time
from dataclasses import dataclass

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(HERE)
for _p in (HERE, CHECKOUT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import parts  # noqa: E402
import reference  # noqa: E402
import traffic  # noqa: E402
import trees  # noqa: E402

KERNEL_WAIT_S = 900.0
LATE_ANSWER_S = 60.0      # how long past the window an answer is awaited
TRACE_DIR = os.path.join(CHECKOUT, ".bench_trace")
TREE_DIR = os.path.join(CHECKOUT, ".bench_scratch", "trees")
ROWS_STREAM, TREE_STREAM = 1, 2        # traffic.rng streams of the seed
PB_CODE = {1: 0, 2: 1, 3: 2}   # pb.Decision value -> reference.CODES
TRACE_OBSERVABILITY = {"enabled": True,
                       "tracing": {"enabled": True, "sample_rate": 0.0}}


class Refused(RuntimeError):
    """The run cannot be measured here (platform, chips, a bad cell)."""


def say(phase: str, **fields) -> None:
    print(f"{phase}: {json.dumps(fields, default=str)}", file=sys.stderr,
          flush=True)


# ------------------------------------------------------------------ spec

def load_spec() -> dict:
    with open(os.path.join(CHECKOUT, "BENCHMARK.json")) as f:
        return json.load(f)


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    mix: dict
    end_to_end: list
    per_layer: list


def load_cell(name: str, spec: dict | None = None,
              scale: dict | None = None) -> Cell:
    """The cell ``name`` with its configuration file and traffic mix;
    ``scale`` (tests only) overlays both at a small size."""
    spec = spec or load_spec()
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise Refused(f"no cell {name!r} in BENCHMARK.json")
    cell = cells[name]
    entry = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    with open(os.path.join(CHECKOUT, entry["file"])) as f:
        config = json.load(f)
    mix = traffic.load_mix(cell["traffic"])
    for target, extra in ((config, (scale or {}).get("config")),
                          (mix, (scale or {}).get("mix"))):
        _overlay(target, extra or {})
    reported = {m["name"] for m in spec["end_to_end"]
                if name in m.get("workloads", [name])}
    e2e = [m for m in spec["end_to_end"] if m["name"] in reported]
    layer = [m for m in spec["per_layer"]
             if name in m.get("workloads", ()) or
             ("workloads" not in m and m["moves"] in reported)]
    return Cell(name, cell["chips"], config, mix, e2e, layer)


def _overlay(target: dict, extra: dict) -> None:
    for key, value in extra.items():
        if isinstance(value, dict) and isinstance(target.get(key), dict):
            _overlay(target[key], value)
        else:
            target[key] = value


def metric_reader(name: str):
    """``metrics/<name>.py``'s ``read(reading)``."""
    return parts.load("metrics", name).read


def reference_module(config: dict):
    """The configuration's plain reference: ``references/<name>.py``
    where it names one, else ``reference.py``."""
    if "reference" in config:
        return parts.load("references", config["reference"])
    return reference


# ---------------------------------------------------------------- device

def device_info(chips: int, allow_cpu: bool) -> dict:
    import jax

    devices = jax.devices()
    info = {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}
    if info["platform"] != "tpu" and not allow_cpu:
        raise Refused(f"JAX found no TPU: {info}")
    if info["count"] < chips:
        raise Refused(f"the cell needs {chips} chips: {info}")
    return info


def enable_compile_cache() -> str:
    """JAX's persistent cache at ``JAX_COMPILATION_CACHE_DIR`` or the
    fixed ``<checkout>/.jax_cache``, holding every program."""
    import jax

    path = (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or os.path.join(CHECKOUT, ".jax_cache"))
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileClock:
    """Backend compiles and persistent-cache loads, from JAX's own
    monitoring events (a copy of chip_smoke.CompileClock, plus loads)."""

    def __init__(self):
        import jax.monitoring
        from jax._src.dispatch import BACKEND_COMPILE_EVENT

        self.compiles, self.compile_s, self.loads, self.load_s = 0, 0.0, 0, 0.0

        def on_event(event, duration, **_):
            if event == BACKEND_COMPILE_EVENT:
                self.compiles += 1
                self.compile_s += duration
            elif event == "/jax/compilation_cache/cache_retrieval_time_sec":
                self.loads += 1
                self.load_s += duration

        jax.monitoring.register_event_duration_secs_listener(on_event)

    def mark(self) -> tuple:
        return (self.compiles, self.compile_s, self.loads, self.load_s)

    def since(self, mark: tuple) -> dict:
        return {"compiles": self.compiles - mark[0],
                "compile_s": self.compile_s - mark[1],
                "cache_loads": self.loads - mark[2],
                "cache_load_s": self.load_s - mark[3]}


def memory_peak_bytes(chips: int) -> int:
    import jax

    peaks = []
    for dev in jax.local_devices()[:chips]:
        stats = dev.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks) if peaks else 0


# --------------------------------------------------------------- serving

class Serving:
    """Worker + gRPC server on loopback + the program's client, stopped
    together (after chip_smoke.Serving)."""

    def __init__(self, overlay: dict):
        from access_control_srv_tpu.srv.config import Config
        from access_control_srv_tpu.srv.transport_grpc import (
            GrpcClient,
            GrpcServer,
        )
        from access_control_srv_tpu.srv.worker import Worker

        cfg = Config.load(os.path.join(CHECKOUT, "cfg"))
        for key, value in overlay.items():
            cfg.set(key, value)
        self.worker = Worker().start(cfg)
        self.server = GrpcServer(self.worker, "127.0.0.1:0").start()
        self.client = GrpcClient(self.server.addr)
        raw = dict(request_serializer=None, response_deserializer=None)
        ch = self.client.channel
        svc = "/acstpu.AccessControlService"
        self.batch_call = ch.unary_unary(f"{svc}/IsAllowedBatch", **raw)

    def stop(self) -> None:
        self.client.close()
        self.server.stop()
        self.worker.stop()

    def counters(self) -> dict:
        snap = self.worker.telemetry.snapshot()
        stages = {k: (v["count"], v["sum_s"])
                  for k, v in (snap.get("stages") or {}).items()}
        return {"paths": dict(snap["paths"]), "stages": stages}

    def wait_kernel(self, n_rules: int) -> float:
        t0 = time.perf_counter()
        while True:
            state = self.worker.evaluator.compile_state()
            if state["current"] and state["kernel"] \
                    and state["n_rules"] >= n_rules:
                return time.perf_counter() - t0
            if time.perf_counter() - t0 > KERNEL_WAIT_S:
                raise RuntimeError(f"kernel never became active: {state}")
            time.sleep(0.02)


def diff_counters(after: dict, before: dict) -> dict:
    paths = {k: v - before["paths"].get(k, 0)
             for k, v in after["paths"].items()}
    stages = {}
    for k, (n, s) in after["stages"].items():
        n0, s0 = before["stages"].get(k, (0, 0.0))
        if n - n0:
            stages[k] = (n - n0, s - s0)
    return {"paths": {k: v for k, v in paths.items() if v},
            "stages": stages}


# -------------------------------------------------------------- workload

@dataclass
class Workload:
    """What the window sends, made before the program starts: a pool of
    batches that the clients send in turn."""
    tree: list                       # nested policy sets (the reference's)
    n_rules: int
    pool: list                       # [rows] per batch
    pool_bytes: list                 # the batches as wire messages


def build_workload(cell: Cell, seed: int) -> Workload:
    mix = cell.mix
    if mix["loop"] != "closed":
        raise Refused(f"the harness drives closed loops only: {mix['loop']}")
    tree = trees.build(cell.config["tree"], traffic.rng(seed, TREE_STREAM))
    n_rules = sum(len(p.get("rules") or []) for ps in tree
                  for p in ps["policies"])
    pool = traffic.make_pool(mix, traffic.rng(seed, ROWS_STREAM))
    return Workload(tree, n_rules, pool,
                    [traffic.batch_bytes(rows) for rows in pool])


# ----------------------------------------------------------------- driver

@dataclass
class Call:
    pool: int
    t_send: float
    t_recv: float = 0.0
    decisions: np.ndarray | None = None     # reference codes, -1 = none
    statuses: np.ndarray | None = None
    error: str | None = None


def parse_batch(payload: bytes, n: int):
    from access_control_srv_tpu.srv.gen import access_control_pb2 as pb

    out = pb.BatchResponse.FromString(payload).responses
    decisions = np.full(n, -1, np.int8)
    statuses = np.zeros(n, np.int32)
    for i, r in enumerate(out[:n]):
        decisions[i] = PB_CODE.get(r.decision, -1)
        statuses[i] = r.operation_status.code
    return decisions, statuses


def annotate(name: str, on: bool):
    if not on:
        return _Null()
    import jax

    return jax.profiler.TraceAnnotation(name)


class _Null:
    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


def closed_loop(serving: Serving, wl: Workload, mix: dict, seconds: float,
                traced: bool):
    """``clients`` threads, each sending the pool's batches in turn and
    its next call only after the last came back, until the window ends."""
    clients, pool = mix["clients"], len(wl.pool_bytes)
    n_rows = mix["batch"]
    calls: list = []
    lock = threading.Lock()
    t0 = time.perf_counter()
    t_end = t0 + seconds

    def client(c: int) -> None:
        j = c
        while time.perf_counter() < t_end:
            call = Call(pool=j % pool, t_send=time.perf_counter())
            j += clients
            try:
                with annotate("bench.call", traced):
                    payload = serving.batch_call(wl.pool_bytes[call.pool],
                                                 timeout=LATE_ANSWER_S + 60)
                call.t_recv = time.perf_counter()
                call.decisions, call.statuses = parse_batch(payload, n_rows)
            except Exception as err:  # noqa: BLE001 — counted, reported
                call.t_recv = time.perf_counter()
                call.error = f"{type(err).__name__}: {err}"[:300]
            with lock:
                calls.append(call)

    threads = [threading.Thread(target=client, args=(c,), daemon=True)
               for c in range(clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(seconds + 2 * LATE_ANSWER_S + 120)
    if any(t.is_alive() for t in threads):
        raise RuntimeError("a client never returned")
    return t0, t_end, calls


# ------------------------------------------------------------ comparison

def expected_codes(module, ref, rows: list) -> np.ndarray:
    """The decision code of every row by ``ref``, a ``Reference`` (or a
    control) of the reference ``module``."""
    return np.array([module.CODES[ref.decide(
        module.Row(r["target"], r["context"]))] for r in rows], np.int8)


def compare_calls(calls: list, expect: list) -> dict:
    """``expect[b]``: the reference's codes of pool batch ``b``.  Every
    row of every call is compared; a row that never came back is missing,
    one with a status other than 200 failed."""
    wrong = missing = failed = compared = 0
    for call in calls:
        n = len(expect[call.pool])
        if call.decisions is None:
            missing += n
            failed += n
            continue
        ok200 = call.statuses == 200
        absent = call.decisions < 0
        missing += int(absent.sum())
        failed += int((~ok200 | absent).sum())
        served = ok200 & ~absent
        compared += int(served.sum())
        wrong += int((served & (call.decisions != expect[call.pool])).sum())
    return {"wrong": wrong, "missing": missing, "failed": failed,
            "compared": compared}


def checks_of(result: dict) -> dict:
    """The numbers compared, each with its limit."""
    return {
        "wrong_decisions": {"value": result["wrong"], "limit": 0,
                            "op": "<="},
        "answers_missing": {"value": result["missing"], "limit": 0,
                            "op": "<="},
        "answers_compared": {"value": result["compared"], "limit": 1,
                             "op": ">="},
    }


def passes(checks: dict) -> bool:
    return all(c["value"] <= c["limit"] if c["op"] == "<="
               else c["value"] >= c["limit"] for c in checks.values())


# ------------------------------------------------------------------- run

@dataclass
class Reading:
    """What a metric reader sees of one run."""
    cell: Cell
    seconds: float
    setup: dict
    window: dict
    counters: dict          # diff over the window
    trace: dict | None

    def stage_seconds(self, *names) -> float | None:
        """Seconds the named stages recorded over the window, or None
        when none of them fired: a stage that records nothing on a path
        is left out, not reported as 0."""
        found = [self.counters["stages"][n][1] for n in names
                 if n in self.counters["stages"]]
        return sum(found) if found else None

    def per_row(self, seconds: float | None, scale: float) -> float | None:
        """``seconds`` per row driven, times ``scale``."""
        n = self.window.get("rows_driven")
        return seconds / n * scale if seconds is not None and n else None


def run(cell_name: str, seed: int, seconds: float, trace: bool,
        allow_cpu: bool = False, scale: dict | None = None,
        spec: dict | None = None) -> dict:
    """One run of ``cell_name``; ``scale`` and ``spec`` (tests only) size
    the cell down and stand in for BENCHMARK.json."""
    cell = load_cell(cell_name, spec or load_spec(), scale)
    device = device_info(cell.chips, allow_cpu)
    enable_compile_cache()
    clock = CompileClock()
    say("device", **device)

    t = time.perf_counter()
    wl = build_workload(cell, seed)
    # a tree or row the reference cannot judge fails here, before set-up
    refs = reference_module(cell.config)
    ref = refs.Reference(wl.tree)
    overlay = dict(cell.config.get("overlay") or {})
    overlay["seed_data"] = trees.seed_data(
        cell.config["tree"], wl.tree, os.path.join(TREE_DIR, cell.name))
    say("harness prep", seconds=time.perf_counter() - t, rules=wl.n_rules)

    if trace:
        overlay["observability"] = TRACE_OBSERVABILITY

    # ---------------------------------------------------- set-up (timed)
    setup_mark = clock.mark()
    t_setup = time.perf_counter()
    serving = Serving(overlay)
    try:
        t_start = time.perf_counter()
        host_compile_s = serving.wait_kernel(wl.n_rules)
        t_warm = time.perf_counter()
        for payload in wl.pool_bytes:   # every shape the window sends
            serving.batch_call(payload, timeout=KERNEL_WAIT_S)
        setup_s = time.perf_counter() - t_setup
        setup = {"setup_s": setup_s,
                 "worker_start_s": t_start - t_setup,
                 "host_compile_s": host_compile_s,
                 "warm_up_s": time.perf_counter() - t_warm,
                 **clock.since(setup_mark)}
        say("set-up", **setup)

        # ------------------------------------------------- the window
        window_mark = clock.mark()
        before = serving.counters()
        trace_dir = start_trace() if trace else None
        try:
            with annotate("bench.window", trace):
                t0, t_end, calls = closed_loop(serving, wl, cell.mix,
                                               seconds, trace)
        finally:
            if trace:
                stop_trace()
        counters = diff_counters(serving.counters(), before)
        in_window = clock.since(window_mark)
        say("window", compiles=in_window["compiles"],
            compile_s=in_window["compile_s"],
            cache_loads=in_window["cache_loads"], paths=counters["paths"])
        peak = memory_peak_bytes(cell.chips)
        if not serving.worker.evaluator.compile_state()["kernel"]:
            say("warning", what="kernel inactive after the window")
    finally:
        serving.stop()

    # ---------------------------- the reference, once the window closed
    t = time.perf_counter()
    result = compare_calls(calls, [expected_codes(refs, ref, rows)
                                   for rows in wl.pool])
    say("reference", seconds=time.perf_counter() - t, **result)
    errors = [c.error for c in calls if c.error]
    if errors:
        say("call errors", n=len(errors), first=errors[0])
    done = [c for c in calls if c.t_recv <= t_end and c.decisions is not None]
    window = {
        "calls": len(calls),
        "calls_in_window": len(done),
        "rows_ok_in_window": int(sum(int((c.statuses == 200).sum())
                                     for c in done)),
        "rows_driven": int(sum(len(c.decisions) for c in calls
                               if c.decisions is not None)),
        "attempted": len(calls) * cell.mix["batch"],
    }
    checks = checks_of(result)

    reduced = None
    if trace:
        import trace_reduce

        reduced = trace_reduce.reduce_dir(trace_dir, device["platform"])
        say("trace", busy_s=reduced["busy_s"], window_s=reduced["window_s"])

    reading = Reading(cell, seconds, setup, window, counters, reduced)
    say("driven", **window)
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        value = metric_reader(m["name"])(reading)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    out = {
        "correct": passes(checks),
        "attempted": window["attempted"],
        "failed": result["failed"],
        "metrics": metrics,
        "device": {**device, "memory_peak_bytes": peak},
    }
    if reduced is not None:
        out["device"]["busy_s"] = reduced["busy_s"]
        out["device"]["window_s"] = reduced["window_s"]
        out["breakdown"] = {"device_ops": reduced["device_ops"],
                            "idle_gaps": reduced["idle_gaps"]}
    out["checks"] = checks
    return out


def start_trace() -> str:
    import shutil

    import jax

    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    os.makedirs(TRACE_DIR)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(TRACE_DIR, profiler_options=opts)
    return TRACE_DIR


def stop_trace() -> None:
    import jax

    jax.profiler.stop_trace()

#!/usr/bin/env python
"""Chip smoke: the system's main path, once, end to end on a TPU.

One process serves the shipped production config (``cfg/config.json``)
through the normal entry points: a ``Worker`` behind ``GrpcServer``, the
seed tree, then BASELINE config 5's tree loaded through the worker's
store (100 000 HR-scoped rules, deny-overrides over permit-overrides).
Over the real gRPC client it sends ``IsAllowedBatch`` calls of 4096
HR-scoped rows and one ``WhatIsAllowed`` batch, flips one rule through
the Rule CRUD service and sends the first batch again.  It checks every
answer's status, the decision mix and 256 sampled rows against the
scalar oracle (``AccessController``), that the flip was served by the
kernel through the delta patch, and that no row took the oracle, hang or
deadline paths.

    python chip_smoke.py              # one chip (what the driver runs)
    python chip_smoke.py --chips 4    # pod_shards:4 and data_devices:4
                                      # against the one-device kernel

The last line of stdout is one JSON object: ``{"ok": true, "device":
{...}}`` on success; ``{"ok": false, "error": ...}`` and a non-zero exit
on any failure, a platform other than TPU included.
"""

from __future__ import annotations

import argparse
import copy
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))

N_RULES = 100_000      # BASELINE.json configs[4]
BATCH = 4096           # cfg/config.json evaluator:micro_batch_max
CALLS = 3
ORACLE_SAMPLE = 256
WIA_ROWS = 32
SEED = 21
KERNEL_WAIT_S = 900.0

DECISIONS = ("PERMIT", "DENY", "INDETERMINATE")
# rows the kernel served: the native wire path, its ceiling retry, and
# the pb path's kernel rows
KERNEL_PATHS = ("native-wire", "native-wire-ceil", "kernel")
FORBIDDEN_PATHS = ("oracle", "oracle-wia", "hang-fallback-oracle",
                   "hang-fallback-degraded", "deadline-expired")


class SmokeFailure(AssertionError):
    pass


def check(cond, what: str, **detail) -> None:
    if not cond:
        raise SmokeFailure(f"{what}: {json.dumps(detail, default=str)}")


def say(phase: str, **fields) -> None:
    print(f"{phase}: {json.dumps(fields, default=str)}", flush=True)


# ------------------------------------------------------------ device

def device_info(chips: int, allow_cpu: bool = False) -> dict:
    """The device as JAX reports it; anything but a TPU with at least
    ``chips`` chips fails (``allow_cpu`` is for the CPU rehearsal)."""
    import jax

    devices = jax.devices()
    info = {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": len(devices)}
    if not allow_cpu:
        check(info["platform"] == "tpu", "JAX found no TPU", **info)
    check(info["count"] >= chips, f"need {chips} devices", **info)
    return info


class CompileClock:
    """Seconds and count of XLA backend compiles, from JAX's own
    monitoring events."""

    def __init__(self):
        import jax.monitoring
        from jax._src.dispatch import BACKEND_COMPILE_EVENT

        self.seconds = 0.0
        self.count = 0

        def on_event(event, duration, **_):
            if event == BACKEND_COMPILE_EVENT:
                self.seconds += duration
                self.count += 1

        jax.monitoring.register_event_duration_secs_listener(on_event)

    def mark(self) -> tuple:
        return (self.count, self.seconds)

    def since(self, mark: tuple) -> dict:
        return {"compiles": self.count - mark[0],
                "compile_s": self.seconds - mark[1]}


# --------------------------------------------------------- workload

def load_config(extra: dict | None = None):
    """``cfg/config.json`` with its seed paths anchored at the checkout
    and ``extra`` blocks overlaid (the four-chip parallel layouts)."""
    from access_control_srv_tpu.srv.config import Config

    cfg = Config.load(os.path.join(REPO, "cfg"))
    for key, path in (cfg.get("seed_data") or {}).items():
        cfg.set(f"seed_data:{key}", os.path.join(REPO, path))
    for key, value in (extra or {}).items():
        cfg.set(key, value)
    return cfg


def build_workload(n_rules: int, batch: int, calls: int, seed: int):
    """The tree document and ``calls`` HR-scoped wire batches
    (``synth``'s generators)."""
    import numpy as np

    from synth import serving_batch_msg, stress_doc

    doc, actual = stress_doc(n_rules, scoped=True)
    rng = np.random.default_rng(seed)
    msgs = [serving_batch_msg(batch, rng, hr=True) for _ in range(calls)]
    return doc, actual, msgs


class Oracle:
    """The in-repo scalar oracle over the same tree, built independently
    of the worker: seed YAML + the stress document straight into an
    ``AccessController``."""

    def __init__(self, doc: dict):
        from access_control_srv_tpu.core import (
            AccessController,
            load_seed_files,
        )
        from access_control_srv_tpu.core.loader import load_policy_sets

        seed = os.path.join(REPO, "data", "seed_data")
        self.engine = AccessController()
        for ps in load_seed_files(
            os.path.join(seed, "policy_sets.yaml"),
            os.path.join(seed, "policies.yaml"),
            os.path.join(seed, "rules.yaml"),
        ):
            self.engine.update_policy_set(ps)
        for ps in load_policy_sets(doc):
            self.engine.update_policy_set(ps)

    @staticmethod
    def _request(msg):
        """A wire request as the service hands it to the engine."""
        from access_control_srv_tpu.srv.service import coerce_request
        from access_control_srv_tpu.srv.transport_grpc import request_from_pb

        return coerce_request(request_from_pb(msg))

    def decide(self, msg) -> tuple:
        resp = self.engine.is_allowed(self._request(msg))
        return resp.decision, resp.operation_status.code

    def what_is_allowed(self, msg):
        from access_control_srv_tpu.srv.transport_grpc import (
            reverse_query_to_pb,
        )

        return reverse_query_to_pb(
            self.engine.what_is_allowed(self._request(msg))
        )

    def update_rule(self, ps_id: str, pol_id: str, rule_doc: dict) -> None:
        from access_control_srv_tpu.core.loader import rule_from_dict

        self.engine.update_rule(ps_id, pol_id, rule_from_dict(rule_doc))


def _triple(msg) -> tuple:
    """(role, entity, action) of one wire request."""
    return (msg.target.subjects[0].value, msg.target.resources[0].value,
            msg.target.actions[0].value)


def choose_flip(doc: dict, oracle: Oracle, msgs):
    """A PERMIT row and a PERMIT rule whose flip to DENY must flip that
    row: within one policy the rule is the row's only matching PERMIT, so
    the policy turns DENY and the deny-overrides set with it.  The oracle
    confirms both answers.  Returns (row index in msgs[0], ps id, policy
    id, flipped rule doc, oracle answer after the flip)."""
    rules_by_triple: dict = {}
    for ps in doc["policy_sets"]:
        for pol in ps["policies"]:
            for rule in pol["rules"]:
                t = rule["target"]
                key = (t["subjects"][0]["value"], t["resources"][0]["value"],
                       t["actions"][0]["value"])
                rules_by_triple.setdefault(key, []).append(
                    (ps["id"], pol["id"], rule)
                )
    for b, msg in enumerate(msgs[0].requests):
        hits = rules_by_triple.get(_triple(msg), [])
        permits: dict = {}
        for ps_id, pol_id, rule in hits:
            if rule["effect"] == "PERMIT":
                permits.setdefault((ps_id, pol_id), []).append(rule)
        sole = [(k, r[0]) for k, r in permits.items() if len(r) == 1]
        if not sole or oracle.decide(msg)[0] != "PERMIT":
            continue
        (ps_id, pol_id), rule = sole[0]
        flipped = {**rule, "effect": "DENY"}
        oracle.update_rule(ps_id, pol_id, flipped)
        after = oracle.decide(msg)
        if after[0] == "DENY":
            return b, ps_id, pol_id, flipped, after
        oracle.update_rule(ps_id, pol_id, rule)  # not decisive: undo
    raise SmokeFailure("no decisive rule flip found in the first batch")


def sample_rows(msgs, n: int, seed: int) -> list:
    import numpy as np

    rng = np.random.default_rng(seed + 1)
    total = sum(len(m.requests) for m in msgs)
    flat = rng.choice(total, size=min(n, total), replace=False)
    per = len(msgs[0].requests)
    return sorted((int(i) // per, int(i) % per) for i in flat)


# ----------------------------------------------------------- serving

class Serving:
    """Worker + gRPC server + client, stopped together."""

    def __init__(self, cfg):
        from access_control_srv_tpu.srv.transport_grpc import (
            GrpcClient,
            GrpcServer,
        )
        from access_control_srv_tpu.srv.worker import Worker

        self.worker = Worker().start(cfg)
        self.server = GrpcServer(self.worker, "127.0.0.1:0").start()
        self.client = GrpcClient(self.server.addr)

    def stop(self) -> None:
        self.client.close()
        self.server.stop()
        self.worker.stop()

    def paths(self) -> dict:
        return self.worker.telemetry.paths.snapshot()

    def load_tree(self, doc: dict) -> float:
        """The tree through the store's seed path (the PRP path);
        returns seconds.  The compile it starts runs async."""
        from synth import store_docs

        t0 = time.perf_counter()
        # a copy: the store owns what it is given, and the next layout
        # loads the same document
        self.worker.store.seed(*store_docs(copy.deepcopy(doc)))
        return time.perf_counter() - t0

    def wait_kernel(self, n_rules: int, timeout_s: float) -> float:
        """Seconds until the kernel serves a compile of the whole tree;
        fails after ``timeout_s`` (a failed async compile never lands)."""
        t0 = time.perf_counter()
        while True:
            state = self.worker.evaluator.compile_state()
            if state["current"] and state["kernel"] \
                    and state["n_rules"] >= n_rules:
                return time.perf_counter() - t0
            check(time.perf_counter() - t0 < timeout_s,
                  "kernel never became active", **state)
            time.sleep(0.05)

    def is_allowed_batch(self, msg) -> list:
        """(decision name, status code) per row."""
        from access_control_srv_tpu.srv.gen import access_control_pb2 as pb

        out = self.client.is_allowed_batch(msg).responses
        check(len(out) == len(msg.requests), "response count",
              sent=len(msg.requests), got=len(out))
        return [(pb.Decision.Name(r.decision), r.operation_status.code)
                for r in out]

    def what_is_allowed_batch(self, msgs) -> list:
        from access_control_srv_tpu.srv.gen import access_control_pb2 as pb

        req = pb.BatchRequest(requests=list(msgs))
        out = self.client._call(
            "acstpu.AccessControlService", "WhatIsAllowedBatch", req,
            pb.BatchReverseQuery,
        )
        return list(out.responses)

    def update_rule(self, rule_doc: dict) -> None:
        from access_control_srv_tpu.srv.gen import access_control_pb2 as pb

        rule = pb.Rule(id=rule_doc["id"], effect=rule_doc["effect"])
        for part in ("subjects", "resources", "actions"):
            for attr in rule_doc["target"][part]:
                getattr(rule.target, part).add(id=attr["id"],
                                               value=attr["value"])
        out = self.client.crud("rule", "Update", pb.RuleList(items=[rule]))
        check(out.operation_status.code == 200, "rule update refused",
              code=out.operation_status.code,
              message=out.operation_status.message)


def _mix(decisions) -> dict:
    n = max(1, len(decisions))
    return {d: sum(1 for x in decisions if x == d) / n for d in DECISIONS}


def serve_and_check(cfg, doc, n_rules, msgs, expect, wia_expect, flip,
                    clock, label: str) -> list:
    """One worker's whole run: load, wait for the kernel, serve, flip,
    check.  ``expect`` maps sampled (call, row) to the oracle's answer,
    ``wia_expect`` holds its reverse queries for the first ``WIA_ROWS``
    rows, ``flip`` is ``choose_flip``'s result.  Returns the decisions of
    every call (pre-flip) for cross-layout comparison."""
    serving = Serving(cfg)
    try:
        mark = clock.mark()
        load_s = serving.load_tree(doc)
        wait_s = serving.wait_kernel(n_rules, KERNEL_WAIT_S)
        say(f"{label} load", tree_load_s=load_s, host_compile_wait_s=wait_s,
            n_rules=serving.worker.evaluator.compile_state()["n_rules"],
            **clock.since(mark))

        base = serving.paths()
        decided = []
        for c, msg in enumerate(msgs):
            mark = clock.mark()
            t0 = time.perf_counter()
            rows = serving.is_allowed_batch(msg)
            say(f"{label} IsAllowedBatch {c}", rows=len(rows),
                seconds=time.perf_counter() - t0, **clock.since(mark))
            bad = [i for i, (_, code) in enumerate(rows) if code != 200]
            check(not bad, "rows without status 200", call=c,
                  n=len(bad), first=bad[:8])
            decided.append([d for d, _ in rows])
        flat = [d for call in decided for d in call]
        mismatches = [
            (c, b, decided[c][b], want)
            for (c, b), want in expect.items() if decided[c][b] != want
        ]
        check(not mismatches, "kernel disagrees with the oracle sample",
              n=len(mismatches), first=mismatches[:8])
        served_mix = _mix(flat)
        sample_mix = _mix(list(expect.values()))
        for d in DECISIONS:
            p = sample_mix[d]
            tol = 4.0 * (p * (1.0 - p) / len(expect)) ** 0.5 + 0.01
            check(abs(served_mix[d] - p) <= tol, "decision mix off",
                  decision=d, served=served_mix[d], sample=p, tol=tol)
        check(served_mix["PERMIT"] > 0 and served_mix["DENY"] > 0,
              "mix lacks PERMIT or DENY", mix=served_mix)
        say(f"{label} decisions", mix=served_mix, oracle_sample=len(expect),
            mismatches=0)

        wia_msgs = list(msgs[0].requests[:WIA_ROWS])
        mark = clock.mark()
        t0 = time.perf_counter()
        wia = serving.what_is_allowed_batch(wia_msgs)
        wia_s = time.perf_counter() - t0
        for i, got in enumerate(wia):
            check(got.operation_status.code == 200, "wia status", row=i,
                  code=got.operation_status.code)
            check(got == wia_expect[i], "wia disagrees with the oracle",
                  row=i)
        say(f"{label} WhatIsAllowedBatch", rows=len(wia), seconds=wia_s,
            oracle_match=len(wia), **clock.since(mark))

        before = serving.worker.evaluator.delta_stats()
        paths_pre = serving.paths()
        row, _, _, flipped, after = flip
        mark = clock.mark()
        t0 = time.perf_counter()
        serving.update_rule(flipped)
        check(serving.worker.evaluator.compile_state()["current"],
              "flip was not applied in place")
        again = serving.is_allowed_batch(msgs[0])
        flip_s = time.perf_counter() - t0
        delta = serving.worker.evaluator.delta_stats()
        paths_post = serving.paths()
        check(again[row] == after, "flip not served", row=row,
              before=decided[0][row], now=again[row], oracle=after)
        check(delta["patches"] == before["patches"] + 1
              and delta["full_compiles"] == before["full_compiles"],
              "flip did not take the delta patch", before=before,
              after=delta)
        kernel_rows = sum(paths_post.get(p, 0) - paths_pre.get(p, 0)
                          for p in KERNEL_PATHS)
        check(kernel_rows == len(again), "flip batch not kernel-served",
              kernel_rows=kernel_rows)
        triple = _triple(msgs[0].requests[row])
        moved = [b for b, (d, _) in enumerate(again) if d != decided[0][b]]
        strays = [b for b in moved
                  if _triple(msgs[0].requests[b]) != triple]
        check(not strays, "flip moved unrelated rows", rows=strays[:8])
        say(f"{label} CRUD flip", rule=flipped["id"], row=row,
            before=decided[0][row], after=again[row][0],
            rows_moved=len(moved), seconds=flip_s, delta_patches=1,
            full_compiles=0, **clock.since(mark))

        paths = serving.paths()
        diff = {k: paths.get(k, 0) - base.get(k, 0)
                for k in set(paths) | set(base)}
        served = len(msgs) * len(msgs[0].requests) + len(again)
        kernel_rows = sum(diff.get(p, 0) for p in KERNEL_PATHS)
        say(f"{label} paths", **{k: v for k, v in sorted(diff.items()) if v})
        check(kernel_rows == served, "rows not served by the kernel",
              kernel_rows=kernel_rows, served=served)
        check(diff.get("kernel-wia", 0) == len(wia),
              "wia rows not served by the reverse kernel", paths=diff)
        forbidden = {p: diff.get(p, 0) for p in FORBIDDEN_PATHS
                     if diff.get(p, 0)}
        check(not forbidden, "rows on the oracle/hang/deadline paths",
              **forbidden)
        check(not serving.worker.evaluator.quarantined,
              "evaluator quarantined")
        return decided
    finally:
        serving.stop()


# ---------------------------------------------------------------- run

LAYOUTS = {
    1: [("one-device", {})],
    4: [("one-device", {}),
        ("pod_shards=4", {"parallel:pod_shards": 4}),
        ("data_devices=4", {"parallel:data_devices": 4})],
}


def run(chips: int, n_rules: int = N_RULES, batch: int = BATCH,
        calls: int = CALLS, n_sample: int = ORACLE_SAMPLE,
        allow_cpu: bool = False) -> dict:
    """Every phase; raises on any failure, returns the device."""
    device = device_info(chips, allow_cpu=allow_cpu)
    say("device", **device)
    clock = CompileClock()

    t0 = time.perf_counter()
    doc, actual, msgs = build_workload(n_rules, batch, calls, SEED)
    oracle = Oracle(doc)
    rows = sample_rows(msgs, n_sample, SEED)
    expect = {(c, b): oracle.decide(msgs[c].requests[b])[0]
              for c, b in rows}
    wia_expect = [oracle.what_is_allowed(m)
                  for m in msgs[0].requests[:WIA_ROWS]]
    flip = choose_flip(doc, oracle, msgs)
    say("workload", rules=actual, batch=batch, calls=calls,
        oracle_sample=len(rows), flip_rule=flip[3]["id"],
        build_s=time.perf_counter() - t0)

    results = {}
    for label, extra in LAYOUTS[chips]:
        results[label] = serve_and_check(
            load_config(extra), doc, actual, msgs, expect, wia_expect, flip,
            clock, label,
        )
    first = next(iter(results.values()))
    for label, decided in results.items():
        diff = sum(a != b for x, y in zip(first, decided)
                   for a, b in zip(x, y))
        check(diff == 0, "layouts disagree", layout=label, rows=diff)
    if len(results) > 1:
        say("layouts agree", layouts=list(results),
            rows=sum(len(c) for c in first))
    return device


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--chips", type=int, choices=sorted(LAYOUTS),
                        default=1)
    args = parser.parse_args(argv)
    code = 1
    try:
        sys.path.insert(0, REPO)
        from access_control_srv_tpu import compile_cache

        compile_cache.enable()
        device = run(args.chips)
        print(json.dumps({"ok": True, "device": {
            "platform": device["platform"], "kind": device["kind"],
            "count": device["count"]}}), flush=True)
        code = 0
    except BaseException as err:  # noqa: BLE001 — reported, exit 1
        import traceback

        traceback.print_exc()
        print(json.dumps({"ok": False,
                          "error": f"{type(err).__name__}: {err}"[:2000]}),
              flush=True)
    sys.stdout.flush()
    sys.stderr.flush()
    # the gRPC and compile threads must not keep the process alive
    os._exit(code)


if __name__ == "__main__":
    main()

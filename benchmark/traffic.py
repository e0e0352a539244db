"""The one traffic generator.  A mix is a JSON file of parameters under
``benchmark/traffic/``; this module turns it and a seed into rows (plain
dicts the reference reads) and wire messages (what the client sends).
Nothing here depends on the program but its wire schema.

A mix (closed loop): ``clients`` threads send ``IsAllowedBatch`` calls of
``batch`` rows, cycling through a ``pool`` of batches made from the seed.
Its rows come from the row kind ``rows.kind`` names: the module
``row_kinds/<kind>.py``, whose ``rows(n, g, params)`` returns ``n`` row
dicts drawn from ``g`` (``params`` is the mix's ``rows`` block).  A row is
what ``row`` builds: target attribute lists, and a context whose subject
and resources go on the wire as JSON, so role-association attributes,
``hierarchical_scopes`` and ``meta.owners`` pass through as they are.
"""

from __future__ import annotations

import json
import os

import numpy as np

import parts

HERE = os.path.dirname(os.path.abspath(__file__))

ORG = "urn:restorecommerce:acs:model:organization.Organization"
SUPER_ADMIN = "superadministrator-r-id"
ROLE = "urn:restorecommerce:acs:names:role"
SUBJECT_ID = "urn:oasis:names:tc:xacml:1.0:subject:subject-id"
ENTITY = "urn:restorecommerce:acs:names:model:entity"
RESOURCE_ID = "urn:oasis:names:tc:xacml:1.0:resource:resource-id"
ACTION_ID = "urn:oasis:names:tc:xacml:1.0:action:action-id"
ACTIONS = [f"urn:restorecommerce:acs:names:action:{a}"
           for a in ("read", "modify", "create", "delete")]


def load_mix(name: str) -> dict:
    with open(os.path.join(HERE, "traffic", f"{name}.json")) as f:
        return json.load(f)


def rng(seed: int, stream: int) -> np.random.Generator:
    """Independent generator streams of one seed (any whole number)."""
    return np.random.default_rng([seed % 2**64, stream])


def row(role, subject, entity, resource, action, ctx_subject,
        ctx_resources) -> dict:
    """One request: its target's role, subject, entity, resource and
    action, and its context's subject and resources (plain dicts)."""
    return {
        "target": {
            "subjects": [{"id": ROLE, "value": role},
                         {"id": SUBJECT_ID, "value": subject}],
            "resources": [{"id": ENTITY, "value": entity},
                          {"id": RESOURCE_ID, "value": resource}],
            "actions": [{"id": ACTION_ID, "value": action}],
        },
        "context": {"subject": ctx_subject, "resources": ctx_resources},
    }


def zipf_draws(g: np.random.Generator, n_items: int, theta: float,
               size: int) -> np.ndarray:
    """Item ranks drawn with P(rank i) proportional to 1/(i+1)**theta."""
    weights = 1.0 / np.arange(1, n_items + 1, dtype=np.float64) ** theta
    return g.choice(n_items, size=size, p=weights / weights.sum())


def make_pool(mix: dict, g: np.random.Generator) -> list:
    """``pool`` batches of ``batch`` rows of the mix's row kind."""
    kind = parts.load("row_kinds", mix["rows"]["kind"])
    return [kind.rows(mix["batch"], g, mix["rows"])
            for _ in range(mix["pool"])]


# ------------------------------------------------------------------ wire

def fill_pb(msg, row: dict) -> None:
    """One pb.Request from a row, field for field as bench_all builds it."""
    target = row["target"]
    for part in ("subjects", "resources", "actions"):
        field = getattr(msg.target, part)
        for attr in target[part]:
            field.add(id=attr["id"], value=attr["value"])
    ctx = row["context"]
    msg.context.subject.value = json.dumps(ctx["subject"]).encode()
    for res in ctx["resources"]:
        msg.context.resources.add().value = json.dumps(res).encode()


def batch_bytes(rows: list) -> bytes:
    from access_control_srv_tpu.srv.gen import access_control_pb2 as pb

    batch = pb.BatchRequest()
    for row in rows:
        fill_pb(batch.requests.add(), row)
    return batch.SerializeToString()

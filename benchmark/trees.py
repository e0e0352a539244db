"""The configurations' policy trees.  A configuration's ``tree`` is one of:

- ``{"seed": "<dir>"}``: a copy of an upstream seed tree, the three flat
  YAML lists (``policy_sets``, ``policies``, ``rules``, children by id)
  under ``benchmark/<dir>``, which the worker boots from as they are;
- ``{"builder": "<name>", "params": {...}}``: ``tree_builders/<name>.py``,
  whose ``build(params, g)`` returns nested policy sets (the shape
  ``seed_doc`` returns) drawn from ``g``, a stream of the run's seed.  The
  built tree is written as the three flat lists into a run directory, and
  the worker boots from those files as it boots from a seed tree's.
"""

from __future__ import annotations

import os

import numpy as np
import yaml

import parts

HERE = os.path.dirname(os.path.abspath(__file__))
SEED_FILES = ("policy_sets", "policies", "rules")


def seed_paths(rel_dir: str) -> dict:
    """The seed tree's YAML files, for the worker's ``seed_data`` block."""
    return {k: os.path.join(HERE, rel_dir, f"{k}.yaml") for k in SEED_FILES}


def seed_doc(rel_dir: str) -> list:
    """The seed YAML (flat lists, children by id) as nested policy sets."""
    flat = {}
    for kind, path in seed_paths(rel_dir).items():
        with open(path) as f:
            flat[kind] = {d["id"]: d for d in yaml.safe_load(f)}
    out = []
    for ps in flat["policy_sets"].values():
        policies = []
        for pid in ps["policies"]:
            pol = dict(flat["policies"][pid])
            pol["rules"] = [flat["rules"][r] for r in pol.get("rules") or []]
            policies.append(pol)
        out.append({**ps, "policies": policies})
    return out


def build(tree: dict, g: np.random.Generator) -> list:
    """The configuration's tree as nested policy sets."""
    if "seed" in tree:
        return seed_doc(tree["seed"])
    builder = parts.load("tree_builders", tree["builder"])
    return builder.build(tree.get("params") or {}, g)


def seed_data(tree: dict, doc: list, run_dir: str) -> dict:
    """The worker's ``seed_data`` block for the configuration's tree: a
    seed tree's own files, or ``doc`` written into ``run_dir``."""
    if "seed" in tree:
        return seed_paths(tree["seed"])
    return write_flat(doc, run_dir)


def write_flat(doc: list, out_dir: str) -> dict:
    """Nested policy sets as the three flat YAML lists that ``seed_doc``
    reads back; their paths."""
    flat = {k: [] for k in SEED_FILES}
    for ps in doc:
        flat["policy_sets"].append(
            {**ps, "policies": [pol["id"] for pol in ps["policies"]]})
        for pol in ps["policies"]:
            rules = pol.get("rules") or []
            flat["policies"].append(
                {**pol, "rules": [rule["id"] for rule in rules]})
            flat["rules"].extend(rules)
    os.makedirs(out_dir, exist_ok=True)
    paths = seed_paths(out_dir)
    for kind, path in paths.items():
        with open(path, "w") as f:
            yaml.dump(flat[kind], f, Dumper=yaml.CSafeDumper, sort_keys=False)
    return paths

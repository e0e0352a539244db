"""A tree written as the three flat YAML lists reads back through
``trees.seed_doc`` as the tree it was: the seed tree, and a built one."""

from __future__ import annotations

import importlib.util
import os

import numpy as np
import pytest

from conftest import BENCH, BIG_SEED

import trees
import traffic


def _dummy_tree() -> list:
    path = os.path.join(BENCH, "tests", "dummy", "tree_builders",
                        "dummy_tree.py")
    spec = importlib.util.spec_from_file_location("dummy_tree", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.build({"policies": 4, "rules": 10, "roles": 6},
                        traffic.rng(BIG_SEED, 2))


TREES = {"seed": lambda: trees.seed_doc("configs/seed_tree"),
         "built": _dummy_tree}


@pytest.mark.parametrize("name", sorted(TREES))
def test_flat_yaml_round_trip(tmp_path, name):
    doc = TREES[name]()
    paths = trees.write_flat(doc, str(tmp_path / "tree"))
    assert paths == trees.seed_paths(str(tmp_path / "tree"))
    assert trees.seed_doc(str(tmp_path / "tree")) == doc


def test_builder_draws_from_the_seed():
    assert _dummy_tree() == _dummy_tree()
    g = np.random.default_rng(0)
    assert trees.build({"seed": "configs/seed_tree"}, g) == \
        trees.seed_doc("configs/seed_tree")

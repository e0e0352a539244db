"""The seed cell reads what it read before trees, row kinds and
references became files of their own: its pool's wire bytes and the
reference's code for every row, at full size (8 batches of 4096 rows),
digested on two seeds."""

from __future__ import annotations

import hashlib

import pytest

from conftest import BIG_SEED, SPEC

import harness

CELL = "seed.batch4096"
PINNED = {   # seed: (wire bytes of every batch, reference codes)
    BIG_SEED: ("face742c31bf54a3bbe85243c71cea26"
               "bba72acaabbaa934423056f8511e51b6",
               "5e9b1921b6653f51d4654d7d67a20a3b"
               "d43444de31a7ee570b1684bcd573feff"),
    3: ("46db23163e14018282447e65bab9c60b"
        "43059fa05ae2a75c6e19bdc381fd273d",
        "5e9b1921b6653f51d4654d7d67a20a3b"
        "d43444de31a7ee570b1684bcd573feff"),
}


@pytest.mark.parametrize("seed", sorted(PINNED))
def test_seed_pool_is_pinned(seed):
    cell = harness.load_cell(CELL, SPEC)
    wl = harness.build_workload(cell, seed)
    refs = harness.reference_module(cell.config)
    ref = refs.Reference(wl.tree)
    wire, codes = hashlib.sha256(), hashlib.sha256()
    assert [len(rows) for rows in wl.pool] == [4096] * 8
    for rows, payload in zip(wl.pool, wl.pool_bytes):
        wire.update(len(payload).to_bytes(8, "little"))
        wire.update(payload)
        codes.update(harness.expected_codes(refs, ref, rows).tobytes())
    assert (wire.hexdigest(), codes.hexdigest()) == PINNED[seed]

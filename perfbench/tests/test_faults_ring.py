"""A four-shard run with the ring broken underneath must come out not
correct, once for each fault only a ring across chips can have: the
exchange left out (every rotation hands the pair back unchanged, so each
device folds its own shard in every round), and the antipodal shard
folded twice (the even-R round R/2 delivers it in both copies, and its
duplicate fold is no longer skipped). The unbroken run passes first."""

from __future__ import annotations

import pytest

from mpi_cuda_largescaleknn_tpu.parallel import ring
from perfbench.tests.test_new_cells import add_ring4_cell_as_files
from perfbench.tests.test_run_cpu import run_cell

FAULTS = {
    "exchange_left_out": ("_rotate_pair", lambda pair, num_shards: pair),
    "antipode_folded_twice": ("_duplicate_round",
                              lambda rnd, num_shards: rnd == 0),
}


@pytest.fixture(autouse=True)
def fresh_programs():
    # the fused program is built once per configuration: a patch reaches
    # it only through a build that starts after it
    ring._ring_program.cache_clear()
    yield
    ring._ring_program.cache_clear()


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_ring_fault_is_not_correct(tiny_root, monkeypatch, fault):
    cell = add_ring4_cell_as_files(tiny_root)
    rc, res, _, err = run_cell(tiny_root, cell)
    assert rc == 0, err
    assert res["correct"] is True

    ring._ring_program.cache_clear()
    monkeypatch.setattr(ring, *FAULTS[fault])
    rc, res, _, err = run_cell(tiny_root, cell)
    assert rc == 0, err
    assert res["correct"] is False
    c = res["checks"]["kth_ulp_max"]
    assert c["value"] > c["limit"]

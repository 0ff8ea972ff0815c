"""The check must fail broken variants of the timed path: each control
(one guarantee of the configuration broken) and each fault a one-chip
cell can have, planted under an otherwise complete run."""
import pytest

from bench.lib import faults


@pytest.mark.parametrize("name,fault,fails", [
    ("f2-4m.ycsb-b-zipf-over-knee", "int16_values", "wrong_answers"),
    ("f2-4m.ycsb-a-zipf", "int16_values", "readback_wrong"),
    ("f2d-4m.ycsb-a-zipf", "fsync_rotate", "unsynced_acks"),
    ("f2-4m.ycsb-a-zipf", "state_unchanged", "readback_wrong"),
    ("f2-4m.ycsb-a-zipf", "half_batch", "wrong_answers"),
    ("f2-4m.ycsb-b-zipf-over-knee", "answer_altered", "wrong_answers"),
])
def test_broken_path_is_not_correct(run_tiny, name, fault, fails):
    res = run_tiny(name, fault=faults.by_name(fault))
    assert not res["correct"]
    assert res["checks"][fails]["value"] > res["checks"][fails]["limit"]
    assert res["failed"] > 0

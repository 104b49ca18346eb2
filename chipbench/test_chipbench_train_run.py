"""Whole training runs of tiny cells on CPU devices, the chip check
skipped: a sound run is correct on one device and on four (ZeRO-1 over VCI
streams); each fault the cells can have makes ``correct`` false."""

import pytest

from chipbench import tiny


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(str(tmp_path_factory.mktemp("tiny")))


@pytest.mark.parametrize("cell,devices", [("tiny-train", 1),
                                          ("tiny-train-dp4", 4)])
def test_sound_run_is_correct(root, cell, devices):
    out = tiny.run_cell(root, cell, seed=2 ** 32 + 9, devices=devices)
    assert out["correct"] is True, out["checks"]
    assert out["failed"] == 0 and out["attempted"] >= 1
    assert set(out["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert set(out["checks"]) == {"loss_gap", "grad_gap", "change_gap"}


@pytest.mark.parametrize("cell,devices,fault", [
    ("tiny-train", 1, "frozen"),
    ("tiny-train", 1, "half"),
    ("tiny-train-dp4", 4, "local"),
])
def test_fault_is_caught(root, cell, devices, fault):
    out = tiny.run_cell(root, cell, devices=devices, fault=fault)
    assert out["correct"] is False, out["checks"]

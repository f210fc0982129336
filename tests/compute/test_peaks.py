"""Peak FLOP/s come from one table keyed by ``device_kind``; a device
the table does not know is an error, and a CPU run prints no MFU."""

import subprocess
import sys
from pathlib import Path

import pytest

from dstack_tpu.models import llama
from dstack_tpu.train.step import (
    DEVICE_PEAKS,
    make_step_callback,
    peak_flops,
)

REPO = Path(__file__).resolve().parents[2]


def test_table_knows_the_v5e_chip():
    # "TPU v5 lite" is what jax.devices()[0].device_kind says on a v5e
    # (chip_smoke.py's device phase prints it)
    assert peak_flops("TPU v5 lite") == 197e12
    assert DEVICE_PEAKS["TPU v5 lite"]["hbm_bytes_per_s"] == 819e9


@pytest.mark.parametrize("kind", ["cpu", "TPU v9 imaginary", ""])
def test_unknown_device_raises(kind):
    with pytest.raises(ValueError, match="no published peak"):
        peak_flops(kind)


def test_callback_without_a_peak_reports_no_mfu():
    cb = make_step_callback(llama.LLAMA_TINY, 512, seq_len=128)
    out = cb(0.5)
    assert "mfu" not in out and out["tokens_per_sec"] == 1024
    assert cb.registry.family("dtpu_train_mfu").value() == 0


def test_finetune_on_cpu_prints_no_mfu(tmp_path):
    proc = subprocess.run(
        [sys.executable, "-m", "dstack_tpu.train.finetune", "--platform",
         "cpu", "--model", "llama-tiny", "--steps", "2", "--log-every", "1",
         "--batch", "8", "--seq-len", "32", "--out", str(tmp_path / "w")],
        cwd=REPO, capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode == 0, proc.stderr[-800:]
    assert "step 2/2 loss=" in proc.stdout
    assert "mfu" not in proc.stdout.lower()
    assert '"platform": "cpu"' in proc.stdout  # the run names its device

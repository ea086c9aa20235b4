"""chip_smoke.py off the chip: it refuses to run without a TPU, and its
phases pass end to end on reduced configs (Pallas kernels in interpret
mode), so a chip call only meets faults the CPU cannot show."""
import os
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from repro.configs import get_reduced

REPO = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO))
import chip_smoke  # noqa: E402


def test_exits_nonzero_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    run = subprocess.run([sys.executable, str(REPO / "chip_smoke.py")],
                         capture_output=True, text=True, env=env,
                         cwd=REPO, timeout=300)
    assert run.returncode == 2, run.stderr[-2000:]
    assert '"ok"' not in run.stdout
    assert "needs a TPU" in run.stderr


def test_smoke_phases_pass_on_reduced_config():
    chip_smoke.smoke(get_reduced("smollm-135m"), chip_smoke.Phases(),
                     train_seq=32, train_batch=2, n_requests=3,
                     plen=(16, 48), max_new=4, slots=2, page=8)


@pytest.mark.skipif(len(jax.devices()) < 2,
                    reason="needs 2 host devices (root conftest pins them)")
def test_four_chip_path_passes_on_two_cpu_devices():
    chip_smoke.four_chips(get_reduced("qwen3-4b"), chip_smoke.Phases(),
                          tp=2, plen=(16, 32), max_new=4, slots=2, page=8)

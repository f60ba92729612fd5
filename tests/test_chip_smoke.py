"""chip_smoke.py's phases at a small size on the CPU.

Each phase drives the same entry points and checks against the same NumPy
references as on the chip; the Pallas kernels run in interpret mode here.
The four-chip phases run in a child process with four host devices.
"""
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))
import chip_smoke  # noqa: E402

AGG = dict(n=1 << 15, modes=("interpret", "off"), tier="interpret")
SMALL = {
    "aggregation": AGG,
    "sort": dict(n=1 << 15),
    "stencil": dict(g=256),
    "cg": dict(n=1 << 16),
    "job": dict(n=1 << 15),
}


@pytest.mark.parametrize("phase", list(SMALL))
def test_phase_matches_numpy_on_one_device(phase):
    getattr(chip_smoke, phase)(np.random.default_rng(0), 1, **SMALL[phase])


def test_aggregation_refuses_a_tier_that_did_not_run():
    with pytest.raises(chip_smoke.SmokeFailure, match=r"segment_reduce\[compiled\]"):
        chip_smoke.aggregation(np.random.default_rng(0), 1, n=1 << 12,
                               modes=("interpret",), tier="compiled")


FOUR = f"""
import numpy as np
import chip_smoke
small = {{"sort": {SMALL['sort']!r}, "cg": {SMALL['cg']!r}, "aggregation": {AGG!r}}}
for phase in chip_smoke.FOUR_CHIP_PHASES:
    getattr(chip_smoke, phase.__name__)(np.random.default_rng(0), 4,
                                        **small[phase.__name__])
print("FOUR_OK")
"""


def test_four_chip_phases_match_numpy_on_four_devices():
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4"}
    r = subprocess.run([sys.executable, "-c", FOUR], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-4000:]
    assert "FOUR_OK" in r.stdout
    assert "output rows per device=[" in r.stdout
    # the padding a partition_by leaves behind takes no exchange capacity,
    # so the Zipf aggregation fits at the default capacity factor
    assert "overflow_retries=0" in r.stdout

"""Test configuration: run on a virtual 8-device CPU mesh.

The reference tests "multi-node without a cluster" by oversubscribing
``mpirun -n 32`` on one host (``scripts/test_cpu.sh``); the TPU analog is
``xla_force_host_platform_device_count`` (SURVEY.md §4).
"""

import os
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

os.environ["JAX_PLATFORMS"] = "cpu"

# Isolate the autotuner persistence: a developer's ~/.cache tuning entry
# must not silently change routing constants inside tests (start() loads
# the cache by default).
if "TORCHMPI_TPU_TUNING_CACHE" not in os.environ:
    import tempfile

    os.environ["TORCHMPI_TPU_TUNING_CACHE"] = os.path.join(
        tempfile.mkdtemp(prefix="tm-test-tuning-"), "autotune.json"
    )
# same isolation for the measured cost-model calibration start() loads
if "TORCHMPI_TPU_CALIBRATION_CACHE" not in os.environ:
    import tempfile

    os.environ["TORCHMPI_TPU_CALIBRATION_CACHE"] = os.path.join(
        tempfile.mkdtemp(prefix="tm-test-calib-"), "calibration.json"
    )
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import pytest  # noqa: E402

# cases a configuration's test files import and run as their own: pytest
# rewrites the assertions of the files it collects, and of these by name
pytest.register_assert_rewrite("decoder_cases", "selected_attention_cases")


@pytest.fixture(autouse=True)
def _fresh_runtime():
    """Each test gets a pristine runtime + constants table."""
    yield
    from torchmpi_tpu import constants, runtime_state
    from torchmpi_tpu.schedule import compiler as _sched_compiler
    from torchmpi_tpu.schedule import cost as _sched_cost

    runtime_state._reset_for_tests()
    constants._reset_for_tests()
    # plan overrides and the measured calibration table are
    # process-global autotuner state like constants
    _sched_compiler.clear_plan_overrides()
    _sched_cost.clear_calibration()
    # the last-checkpoint registry is process-global too
    from torchmpi_tpu.supervise import checkpoints as _ckpts

    _ckpts._reset_for_tests()


def pytest_sessionfinish(session, exitstatus):
    """Lock-order gate: under TORCHMPI_TPU_LOCK_MONITOR=1 (how CI runs
    tier-1 once), any inversion the monitored locks recorded fails the
    session — even one raised inside a worker thread and swallowed
    there. The violation record names both orders and both sites."""
    from torchmpi_tpu.analysis import lockmon

    bad = lockmon.violations()
    if bad:
        import json

        print(
            "\nLOCK-ORDER INVERSIONS recorded by the runtime monitor:\n"
            + json.dumps(bad, indent=2),
            file=sys.stderr,
        )
        if exitstatus == 0:
            session.exitstatus = 3

"""Test config: force an 8-device virtual CPU mesh.

Mirrors the reference's single-local-Spark-session test harness
(utils/.../test/TestSparkContext.scala:46 `master=local[2]`): distribution is
validated on emulated devices, matching how the driver dry-runs the
multi-chip path (xla_force_host_platform_device_count).

force_cpu pins the platform through the live jax config as well as the
environment, so the mesh holds even when jax was imported before this file.
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from transmogrifai_tpu.utils.platform import force_cpu  # noqa: E402

force_cpu(8)

# the plan-time autotuner (docs/planning.md) must see a COLD corpus in
# tests: tier-1 behavior is pinned to the hand defaults, not to whatever
# measurements this box's bench/calibrate runs have accumulated in the
# user-level cache dir (the planner tests build their own corpora)
import tempfile  # noqa: E402

os.environ["TMOG_PLAN_CORPUS_DIR"] = tempfile.mkdtemp(
    prefix="tmog_test_plan_corpus_")

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture
def rng():
    return np.random.default_rng(42)


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long-running test (multi-process bring-up etc.)")

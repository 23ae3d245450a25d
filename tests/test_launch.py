"""Launch-path tests: plans, specs, mini dry-run on an 8-device mesh."""
import os
import subprocess
import sys

import jax

from repro.configs import SHAPES, get_config
from repro.launch.mesh import make_plan

jax.config.update("jax_platform_name", "cpu")

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_plan_rules_no_mesh():
    cfg = get_config("gemma2-2b")
    plan = make_plan(cfg, SHAPES["train_4k"], None)
    assert plan.mesh is None and plan.tp == 1


def test_mini_dryrun_subprocess():
    """Full launch path (lower+compile+analyze) on an 8-device host mesh."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tests", "_launch_worker.py")],
        capture_output=True, text=True, env=env, timeout=1800,
    )
    assert out.returncode == 0, f"stdout:\n{out.stdout}\nstderr:\n{out.stderr}"
    assert "ALL OK" in out.stdout

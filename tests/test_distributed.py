"""Multi-device behaviour, exercised in a subprocess so the 8 fake CPU
devices never leak into this process (device count locks at first jax init;
the dry-run has its own 512-device entrypoint for the same reason)."""

import os
import subprocess
import sys

import pytest

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def dist_output():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(_ROOT, "src")
    env.pop("XLA_FLAGS", None)          # the script sets its own
    proc = subprocess.run(
        [sys.executable, os.path.join(_ROOT, "scripts", "dist_checks.py")],
        capture_output=True, text=True, timeout=1200, env=env)
    assert proc.returncode == 0, proc.stdout + "\n" + proc.stderr
    return proc.stdout


def test_vmp_distributed_parity(dist_output):
    assert "PASS vmp_parity" in dist_output


def test_svi_distributed_parity(dist_output):
    assert "PASS svi_parity" in dist_output


def test_svi_outofcore_parity(dist_output):
    assert "PASS svi_outofcore_parity" in dist_output


def test_svi_staging_plan_bitwise(dist_output):
    assert "PASS svi_staging_plan" in dist_output


def test_vmp_collectives(dist_output):
    assert "PASS vmp_collectives" in dist_output


def test_lm_train_2d_mesh(dist_output):
    assert "PASS lm_train_2d_mesh" in dist_output


def test_elastic_remesh(dist_output):
    assert "PASS elastic_remesh" in dist_output


def test_long_context_sp_decode(dist_output):
    assert "PASS long_context_sp_decode" in dist_output


def test_all_pass(dist_output):
    assert "ALL DIST CHECKS PASS" in dist_output

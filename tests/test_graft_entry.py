"""Driver-contract tests: entry() compiles and runs; dryrun_multichip
builds a real dp/tp/sp mesh and executes one sharded training step."""

import sys

import numpy as np
import pytest

sys.path.insert(0, "/root/repo")

import __graft_entry__ as graft
from tensorframes_tpu.parallel import device_count


def test_entry_jittable():
    import jax

    fn, args = graft.entry()
    out = jax.jit(fn)(*args)
    out = np.asarray(out)
    assert out.ndim == 2 and np.isfinite(out).all()


@pytest.mark.skipif(device_count() < 8, reason="needs 8 virtual devices")
def test_dryrun_multichip_8():
    graft.dryrun_multichip(8)


def test_dryrun_multichip_1(monkeypatch):
    """Enough devices exist (conftest provides 8): the dry run uses
    them and touches neither ``jax_platforms`` nor ``XLA_FLAGS``."""
    import os

    import jax

    touched = []
    real = jax.config.update

    def spy(name, value):
        touched.append(name)
        return real(name, value)

    monkeypatch.setattr(jax.config, "update", spy)
    flags = os.environ.get("XLA_FLAGS", "")
    graft.dryrun_multichip(1)
    assert "jax_platforms" not in touched
    assert os.environ.get("XLA_FLAGS", "") == flags


def test_dryrun_multichip_16_subprocess():
    """16 virtual devices (VERDICT r2 #9): the conftest pins this process
    to 8, so the 16-way case runs in a fresh subprocess the way the
    driver invokes it."""
    import os
    import subprocess

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=16"
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    r = subprocess.run(
        [sys.executable, "-c",
         "import __graft_entry__ as g; g.dryrun_multichip(16)"],
        cwd=repo_root,
        env=env,
        capture_output=True,
        text=True,
        timeout=900,
    )
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    assert "dryrun frozen-graph OK" in r.stdout

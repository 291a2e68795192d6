"""The dryrun's parity assertions must be able to catch a wrong-but-finite
sharding bug (finite-only checks can't): a deliberately desynced shard fails
part A fast."""
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_injected_shard_desync_fails_parity():
    code = ("from __graft_entry__ import dryrun_multichip; "
            "dryrun_multichip(8)")
    env = {**os.environ, "GRAFT_DRYRUN_INJECT_FAULT": "1",
           "JAX_PLATFORMS": "cpu"}
    r = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=500)
    assert r.returncode != 0, "fault-injected dryrun unexpectedly passed"
    assert "parity FAIL" in (r.stdout + r.stderr)


@pytest.mark.slow
def test_every_layout_matches_its_replicated_run():
    """The positive path: all seven parts, parity OK (minutes on the CPU)."""
    code = ("from __graft_entry__ import dryrun_multichip; "
            "dryrun_multichip(8)")
    r = subprocess.run([sys.executable, "-c", code], cwd=ROOT,
                       env={**os.environ, "JAX_PLATFORMS": "cpu"},
                       capture_output=True, text=True, timeout=1800)
    assert r.returncode == 0, (r.stdout + r.stderr)[-3000:]
    assert "dryrun_multichip OK" in r.stdout

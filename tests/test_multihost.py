"""Multi-process distributed backend test: the 2-process dryrun runs the
full sharded rollout+learn step with cross-process collectives (gRPC/Gloo
standing in for ICI/DCN) and reproduces the single-process result."""
import os
import re
import subprocess
import sys

import pytest

pytestmark = pytest.mark.slow  # ~86 s: real 2-process gRPC dryrun

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_two_process_dryrun_matches_single_process():
    env = {k: v for k, v in os.environ.items()
           if k != "XLA_FLAGS"}
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "dryrun_multihost.py"),
         "--procs", "2", "--devices-per-proc", "2", "--timeout", "450"],
        capture_output=True, text=True, timeout=560, env=env)
    assert r.returncode == 0, (r.stdout[-2000:], r.stderr[-2000:])
    m = re.search(r"dryrun_multihost\(2x2\): ok — return=([-\d.]+) "
                  r"critic_loss=([-\d.]+)", r.stdout)
    assert m, r.stdout[-2000:]
    # the sharded step is process-count-invariant: 2 procs x 2 devices
    # equals the proven single-process 4-device dryrun (same seeds, same
    # replica shards — only the process boundary moves)
    ret, loss = float(m.group(1)), float(m.group(2))
    r1 = subprocess.run(
        [sys.executable, "-c",
         "import sys; sys.path.insert(0, %r); "
         "import __graft_entry__ as g; g.dryrun_multichip(4)" % REPO],
        capture_output=True, text=True, timeout=560, env=env)
    assert r1.returncode == 0, (r1.stdout[-2000:], r1.stderr[-2000:])
    m1 = re.search(r"ok — return=([-\d.]+) critic_loss=([-\d.]+)",
                   r1.stdout)
    assert m1, r1.stdout
    assert abs(ret - float(m1.group(1))) < 5e-3
    assert abs(loss - float(m1.group(2))) < 5e-3

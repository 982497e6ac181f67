"""Cross-process asynchronous parameter server: a master process owns the
accumulator behind a TCP PSServer; worker processes pull version-tagged
snapshots and push gradients through PSClient — the reference's
Aeron-backed ParameterServerParallelWrapper topology
(ParameterServerParallelWrapper.java:159-160) over a socket transport.

This example spawns ONE real worker subprocess against an in-process
server (the 2-process convergence test in tests/test_ps_transport.py runs
the full two-worker topology). The worker is the test suite's
`tests/ps_remote_worker.py`, which pins itself to the CPU backend: a chip
belongs to one process at a time, so a worker that wanted this process's
chip could not have it.
"""
import _common  # noqa: F401

import os
import subprocess
import sys

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "tests"))

from ps_remote_server import build_data, build_net  # noqa: E402

from deeplearning4j_tpu.parallel import PSServer  # noqa: E402

net = build_net()
ds = build_data()
s0 = float(net.score(ds))
srv = PSServer(net, queue_size=4, n_workers=1)

env = {k: v for k, v in os.environ.items()
       if k not in ("XLA_FLAGS", "JAX_PLATFORMS")}
env["PYTHONPATH"] = REPO + os.pathsep + os.path.join(REPO, "tests")
worker = subprocess.run(
    [sys.executable, os.path.join(REPO, "tests", "ps_remote_worker.py"),
     "0", "1", str(srv.port)],
    capture_output=True, text=True, env=env, timeout=240)
assert worker.returncode == 0, worker.stdout + worker.stderr
stats = srv.wait(timeout=60)

s1 = float(net.score(ds))
print(f"score {s0:.4f} -> {s1:.4f}; applied={stats['applied']} "
      f"stale_dropped={stats['stale_dropped']} "
      f"max_staleness={stats['max_staleness_seen']}")
assert s1 < s0 and stats["applied"] + stats["stale_dropped"] == 24
print(True)

"""Multi-host (multi-process) distributed backend: real cross-process run.

Spawns TWO separate Python processes, each with 4 virtual CPU devices, wired
together by jax.distributed (Gloo over localhost — the CPU stand-in for DCN).
They build one 8-device process-spanning (dp=4, tp=2) mesh, run two sharded
training steps with per-process data feeding, and must agree on the loss —
which must also match a single-process 8-device run on the same seed. This is
the multi-host capability the reference's (never-configured) NCCL layer was
for (SURVEY.md §2.3), validated without TPUs.
"""

import os
import re
import socket
import subprocess
import sys
from pathlib import Path

import pytest

import numpy as np
import pytest

REPO = Path(__file__).resolve().parent.parent


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _env(n_devices: int):
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_devices}"
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("PYTEST_CURRENT_TEST", None)
    return env


@pytest.mark.slow
def test_two_process_mesh_matches_single_process():
    # slow AND capability-gated: a CPU backend without the collectives
    # plugin rejects multi-process computations outright ("Multiprocess
    # computations aren't implemented on the CPU backend"), so this lives
    # behind -m slow, outside tier-1.
    port = _free_port()
    cmd = [sys.executable, "-m",
           "aws_k8s_ansible_provisioner_tpu.parallel.multihost",
           "--coordinator", f"localhost:{port}", "--num-processes", "2"]
    procs = [subprocess.Popen(cmd + ["--process-id", str(i)],
                              cwd=REPO, env=_env(4),
                              stdout=subprocess.PIPE,
                              stderr=subprocess.STDOUT, text=True)
             for i in range(2)]
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=420)
            outs.append(out)
    finally:
        # a failing/hung worker must not orphan its peer (which would block
        # forever in the coordinator handshake) nor leak the bound port
        for p in procs:
            if p.poll() is None:
                p.kill()
    for p, out in zip(procs, outs):
        assert p.returncode == 0, f"worker failed:\n{out[-2000:]}"

    losses = []
    for out in outs:
        m = re.search(r"MULTIHOST_SELFTEST process=\d/2 devices=8 "
                      r"loss=([-\d.]+)", out)
        assert m, f"no selftest line in:\n{out[-2000:]}"
        losses.append(float(m.group(1)))
    assert losses[0] == losses[1], f"processes disagree: {losses}"

    # single-process reference on the same seed: one process, 8 devices,
    # same mesh/data -> same loss
    ref = subprocess.run(
        [sys.executable, "-m",
         "aws_k8s_ansible_provisioner_tpu.parallel.multihost",
         "--coordinator", f"localhost:{_free_port()}",
         "--num-processes", "1", "--process-id", "0"],
        cwd=REPO, env=_env(8), capture_output=True, text=True, timeout=420)
    assert ref.returncode == 0, ref.stdout + ref.stderr
    m = re.search(r"loss=([-\d.]+)", ref.stdout)
    assert m, f"no loss line in:\n{ref.stdout[-2000:]}"
    np.testing.assert_allclose(losses[0], float(m.group(1)), rtol=1e-5)

"""`python -m jimm_tpu.launch`: the local/multi-node process-group
launcher (torchrun counterpart; SURVEY §2.3 collective backend row)."""

import subprocess
import sys

import pytest

from jimm_tpu import launch

CHILD = r"""
import jax
from jimm_tpu.parallel import initialize_distributed, make_mesh
initialize_distributed()   # coordinator/world/rank all from launcher env
assert jax.process_count() == 2, jax.process_count()
assert jax.local_device_count() == 2
import numpy as np
from jax import shard_map
from jax.sharding import PartitionSpec as P
mesh = make_mesh({"data": -1})
out = jax.jit(shard_map(lambda x: jax.lax.psum(x, "data"), mesh=mesh,
                        in_specs=P(), out_specs=P()))(np.float32(1.0))
assert float(out) == 4.0, float(out)
print("RANK_DONE", jax.process_index())
"""


@pytest.mark.slow
def test_launch_two_process_group():
    """2 processes x 2 virtual devices: bare initialize_distributed() in
    the child joins the launcher's cluster and a cross-process psum runs."""
    proc = subprocess.run(
        [sys.executable, "-m", "jimm_tpu.launch", "--nproc", "2",
         "--platform", "cpu", "--host-devices", "2", "--",
         sys.executable, "-c", CHILD],
        capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    for rank in (0, 1):
        assert f"[rank {rank}] RANK_DONE {rank}" in proc.stdout


def test_launch_fails_fast_on_child_failure():
    """A failing rank must take the group down and propagate its code (a
    dead rank would otherwise hang the others inside a collective)."""
    proc = subprocess.run(
        [sys.executable, "-m", "jimm_tpu.launch", "--nproc", "2",
         "--platform", "cpu", "--",
         sys.executable, "-c", "import sys; sys.exit(3)"],
        capture_output=True, text=True, timeout=120)
    assert proc.returncode == 3
    assert "terminating the group" in proc.stderr


def test_launch_arg_validation():
    cases = [
        ["--nproc", "2"],                                   # no command
        ["--nproc", "1", "--", "true"],                     # 1-process world
        ["--nnodes", "2", "--nproc", "1", "--", "true"],    # no coordinator
        ["--nnodes", "2", "--node-rank", "2", "--coordinator", "h:1",
         "--nproc", "1", "--", "true"],                     # rank out of range
        # one process per TPU host: a second local process cannot get chips
        ["--nproc", "2", "--platform", "tpu", "--", "true"],
    ]
    for argv in cases:
        with pytest.raises(SystemExit):
            launch.main(argv)


def test_launch_rank_assignment_across_nodes():
    """Global ranks are node_rank * nproc + local — verified via the env
    the launcher exports (children just echo it)."""
    proc = subprocess.run(
        [sys.executable, "-m", "jimm_tpu.launch", "--nproc", "2",
         "--nnodes", "2", "--node-rank", "1", "--coordinator",
         "127.0.0.1:1", "--",
         sys.executable, "-c",
         "import os; print('ENV', os.environ['JIMM_PROCESS_ID'], "
         "os.environ['JIMM_NUM_PROCESSES'], os.environ['JIMM_COORDINATOR'])"],
        capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "[rank 2] ENV 2 4 127.0.0.1:1" in proc.stdout
    assert "[rank 3] ENV 3 4 127.0.0.1:1" in proc.stdout


def test_explicit_platform_args_survive_env_bootstrap():
    """A child's explicit --host-devices must not be clobbered when
    initialize_distributed()'s env bootstrap re-runs configure_platform
    with the launcher's JIMM_* vars still set."""
    code = (
        "import os\n"
        "os.environ['JIMM_PLATFORM'] = 'cpu'\n"
        "os.environ['JIMM_HOST_DEVICES'] = '2'\n"
        "from jimm_tpu.utils.env import configure_platform\n"
        "configure_platform(platform='cpu', host_devices=4)  # explicit\n"
        "configure_platform()  # env-only bootstrap must not override\n"
        "import jax\n"
        "assert jax.device_count() == 4, jax.device_count()\n"
        "print('PRECEDENCE_OK')\n")
    proc = subprocess.run([sys.executable, "-c", code],
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "PRECEDENCE_OK" in proc.stdout

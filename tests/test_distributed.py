"""Multi-process distributed tests.

Reference parity: tests/nightly/dist_sync_kvstore.py launched through
`tools/launch.py -n 2 --launcher local` (SURVEY.md §4 — multi-node
without a cluster).  Spawns real processes that rendezvous via
jax.distributed, so the cross-process all-reduce path
(kvstore._cross_process_allreduce) is exercised for real, not mocked.
"""

import os
import signal
import socket
import subprocess
import sys

import pytest

from mxnet_tpu.test_utils import cpu_child_env

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _free_port():
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


@pytest.mark.parametrize("n_workers", [2])
def test_dist_sync_kvstore_multiprocess(n_workers):
    proc = subprocess.run(
        [sys.executable, os.path.join(_REPO, "tools", "launch.py"),
         "-n", str(n_workers), "--launcher", "local",
         "--port", str(_free_port()), "--",
         sys.executable, os.path.join(_REPO, "tests",
                                      "dist_sync_kvstore.py")],
        env=cpu_child_env(), capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, (proc.stdout[-2000:],
                                  proc.stderr[-2000:])
    for rank in range(n_workers):
        assert f"worker {rank}/{n_workers}: dist_sync_kvstore OK" \
            in proc.stdout


# -- fault tolerance (mxnet_tpu/resilience.py) ---------------------------------

_WORKER = os.path.join(_REPO, "tests", "resilient_dist_worker.py")


@pytest.mark.slow
def test_dist_survivor_exits_via_watchdog(tmp_path):
    """SIGTERM one worker mid-run: the survivor's next collective wedges
    waiting on the dead peer, and the MXTPU_COLLECTIVE_TIMEOUT watchdog
    must abort it (stack dump + exit code 42), not let it hang."""
    port = _free_port()
    env = cpu_child_env()
    env.update({
        "MXTPU_COORDINATOR": f"127.0.0.1:{port}",
        "MXTPU_NUM_WORKERS": "2",
        "MXTPU_COLLECTIVE_TIMEOUT": "8",
        "MXTPU_WATCHDOG_ACTION": "abort",
        "MXTPU_WATCHDOG_EXIT_CODE": "42",
    })
    procs = []
    for rank in range(2):
        e = dict(env)
        e["MXTPU_WORKER_RANK"] = str(rank)
        procs.append(subprocess.Popen(
            [sys.executable, _WORKER, str(tmp_path), "40"],
            env=e, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
            text=True))
    outs = [p.communicate(timeout=120) for p in procs]
    # rank 1 died of its self-delivered SIGTERM
    assert procs[1].returncode == -signal.SIGTERM, outs[1]
    # rank 0 did NOT hang: the collective watchdog aborted it with the
    # configured exit code after dumping where it was stuck
    assert procs[0].returncode == 42, (procs[0].returncode, outs[0])
    assert "watchdog" in outs[0][1] and "expired" in outs[0][1]
    assert "thread stack dump" in outs[0][1]


@pytest.mark.slow
def test_dist_gang_restart_resumes_from_checkpoint(tmp_path):
    """launch.py --max-restarts 1: worker 1 crashes mid-run, the gang is
    torn down and relaunched, both ranks resume from their latest
    checkpoint and reach the final step with the exact state a serial
    replay produces."""
    num_steps = 40
    proc = subprocess.run(
        [sys.executable, os.path.join(_REPO, "tools", "launch.py"),
         "-n", "2", "--launcher", "local", "--max-restarts", "1",
         "--port", str(_free_port()), "--",
         sys.executable, _WORKER, str(tmp_path), str(num_steps)],
        env={**cpu_child_env(),
             "MXTPU_COLLECTIVE_TIMEOUT": "8",
             "MXTPU_WATCHDOG_ACTION": "abort"},
        capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, (proc.stdout[-2000:],
                                  proc.stderr[-2000:])
    assert "restarting gang" in proc.stderr
    for rank in range(2):
        assert f"worker {rank}: resilient run done at step {num_steps}" \
            in proc.stdout
        assert f"worker {rank}: resumed from step" in proc.stdout

    # both ranks' final checkpoints match an uninterrupted serial replay
    sys.path.insert(0, _REPO)
    try:
        from mxnet_tpu import resilience
    finally:
        sys.path.pop(0)
    import numpy as np

    w = np.full(4, 10.0)
    for _ in range(num_steps):
        w = w - 0.05 * 2 * w
    for rank in range(2):
        ck = resilience.LocalCheckpointer(
            os.path.join(str(tmp_path), f"rank{rank}"))
        assert ck.latest_step() == num_steps
        np.testing.assert_allclose(ck.restore(num_steps)["w"], w,
                                   rtol=1e-12)

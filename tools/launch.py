#!/usr/bin/env python
"""Distributed job launcher.

Reference parity: tools/launch.py + dmlc-core tracker — spawns the
scheduler/server/worker processes for dist kvstore.

TPU-native redesign (SURVEY.md §2.6): there is no parameter server; a
"distributed job" is N identical processes joining one
``jax.distributed.initialize`` rendezvous (coordinator address replaces the
dmlc tracker).  Supported launchers: ``local`` (N processes on this host —
the analog of the reference's fake-multi-node nightly tests) and ``ssh``.

One process holds a TPU host's chips at a time, and every ``local`` rank
is handed this launcher's full environment.  ``--launcher local`` is
therefore for CPU gangs (``JAX_PLATFORMS=cpu``, as the tests run it);
on TPUs use one rank per host (``ssh``).  The launcher itself never
imports jax, so it holds no chip.

Two supervision modes for ``local``:

- default (gang fate-sharing): one nonzero worker exit tears down the
  whole gang; ``--max-restarts`` relaunches the FULL gang on a fresh
  port and workers resume from their checkpoints.
- ``--elastic``: workers share a gang control-plane directory
  (``MXTPU_GANG_DIR``) and survive peer death in-job
  (mxnet_tpu/resilience.ElasticGang).  A dead rank does NOT take the
  gang down; the launcher respawns ONLY that rank (after a delay that
  lets the survivors agree the shrink epoch first), and the respawn
  rejoins through the gang's join protocol.
"""

from __future__ import annotations

import argparse
import os
import subprocess
import sys
import tempfile
import time


def _spawn_worker(cmd, rank, num_workers, port, extra_env=None):
    """Spawn ONE worker with the gang env contract (plus the launcher's
    whole environment: on a TPU host N local ranks would contend for
    the chips — see the module docstring)."""
    env = dict(os.environ)
    env.update({
        "MXTPU_COORDINATOR": f"127.0.0.1:{port}",
        "MXTPU_NUM_WORKERS": str(num_workers),
        "MXTPU_WORKER_RANK": str(rank),
    })
    if extra_env:
        env.update(extra_env)
    return subprocess.Popen(cmd, env=env)


def _spawn_gang(cmd, num_workers, port, extra_env=None):
    """Spawn one full gang of workers sharing a rendezvous on ``port``."""
    return [_spawn_worker(cmd, rank, num_workers, port, extra_env)
            for rank in range(num_workers)]


def _terminate_gang(procs, grace=10.0):
    """SIGTERM every live worker, then SIGKILL stragglers after grace."""
    for p in procs:
        if p.poll() is None:
            try:
                p.terminate()
            except OSError:
                pass
    deadline = time.monotonic() + grace
    for p in procs:
        if p.poll() is None:
            remaining = max(0.0, deadline - time.monotonic())
            try:
                p.wait(timeout=remaining)
            except subprocess.TimeoutExpired:
                p.kill()
                p.wait()


def _supervise_gang(procs, grace=10.0, poll_interval=0.2):
    """Wait out one gang attempt; returns the attempt's failure code.

    Exit-code semantics, explicitly: ONLY a nonzero exit counts as a
    failure — a worker that finishes cleanly (exit 0) after its peers
    die is complete, not failed.  The first nonzero exit triggers gang
    fate-sharing teardown of the survivors; the codes those survivors
    then die with (-SIGTERM/-SIGKILL) are artifacts of OUR teardown and
    are never reported as the failure.  Returns 0 when every worker
    exited 0.
    """
    live = dict(enumerate(procs))      # rank -> proc
    failed = 0
    while live:
        time.sleep(poll_interval)
        for rank, p in list(live.items()):
            code = p.poll()
            if code is None:
                continue
            del live[rank]
            if code != 0 and not failed:
                failed = code
                sys.stderr.write(
                    f"[launch] rank {rank} exited rc={code}\n")
        if failed and live:
            # gang fate-sharing: survivors are wedged in collectives
            # waiting on the dead rank — tear them down now (their
            # teardown exit codes are not failures, see above)
            _terminate_gang(list(live.values()), grace=grace)
            live.clear()
    return failed


def launch_local(args, cmd):
    """Spawn n worker processes on localhost, each with the env
    jax.distributed expects (reference: dmlc tracker 'local' mode env
    DMLC_ROLE/DMLC_PS_ROOT_URI → MXTPU_COORDINATOR/RANK/WORLD).

    Gang supervision: a distributed job is all-or-nothing — one dead
    worker wedges every surviving collective.  When any worker exits
    nonzero the whole gang is torn down, and with ``--max-restarts N``
    the full gang is relaunched (workers are expected to resume from
    their latest checkpoint; see mxnet_tpu/resilience.py).  Each attempt
    uses ``port + attempt`` so a lingering coordinator socket from the
    dead gang can't poison the new rendezvous.
    """
    for attempt in range(args.max_restarts + 1):
        procs = _spawn_gang(cmd, args.num_workers, args.port + attempt)
        failed = _supervise_gang(procs, grace=args.grace)
        if not failed:
            return 0
        if attempt < args.max_restarts:
            sys.stderr.write(
                f"[launch] worker exited rc={failed}; restarting gang "
                f"(attempt {attempt + 2}/{args.max_restarts + 1}, "
                f"port {args.port + attempt + 1})\n")
    return failed


def _import_distributed():
    """Load mxnet_tpu.distributed without the package __init__ (the
    launcher host needs no jax)."""
    import importlib
    import types

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if "mxnet_tpu" not in sys.modules:
        pkg = types.ModuleType("mxnet_tpu")
        pkg.__path__ = [os.path.join(root, "mxnet_tpu")]
        sys.modules["mxnet_tpu"] = pkg
    return importlib.import_module("mxnet_tpu.distributed")


def _start_kv_daemon(addr):
    """Spawn the embedded gang-KV daemon (tools/gang_kv.py); returns
    (proc, bound_addr) once it prints its LISTEN line."""
    script = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "gang_kv.py")
    proc = subprocess.Popen(
        [sys.executable, script, "--addr", addr or "127.0.0.1:0"],
        stdout=subprocess.PIPE, text=True)
    line = proc.stdout.readline()
    if not line.startswith("LISTEN "):
        proc.kill()
        raise RuntimeError(f"gang KV daemon failed to start: {line!r}")
    return proc, line.split()[1]


def launch_elastic(args, cmd):
    """Elastic supervision: peer death shrinks the gang instead of
    killing it; the launcher's job is only to (a) provision the control
    plane, (b) respawn dead ranks so the gang can grow back, and (c)
    act on ScalePolicy grow requests.

    - Control plane: ``--kv file`` (default) exports ``MXTPU_GANG_DIR``
      (created if ``--gang-dir`` is not given); ``--kv tcp`` embeds the
      tools/gang_kv.py daemon and exports ``MXTPU_GANG_KV=tcp`` +
      ``MXTPU_GANG_ADDR`` — no shared filesystem.  The daemon is NOT
      restarted if it dies: the ranks' deterministic coordinator
      failover (distributed.TcpKV) is the recovery story.
    - ``MXTPU_ELASTIC=1`` is exported to every worker.
    - A rank that exits 0 is COMPLETE (including a rank the gang evicted
      — GangEvicted exits cleanly); it is never respawned.
    - A rank that dies (nonzero / signal) while peers are still running
      is absorbed by the gang; up to ``--max-restarts`` such ranks are
      respawned — same rank id, same port — after
      ``MXTPU_ELASTIC_RESPAWN_DELAY`` seconds (default 1.5x the
      heartbeat timeout) so the survivors commit the shrink epoch before
      the rejoin request lands.
    - A ``scale/req`` record in the KV (resilience.ScalePolicy) spawns a
      NEW rank id, which enters through the gang's join protocol.
    - The launcher fails (returns the exit code) only when a rank dies
      with NO surviving peers to absorb it, or a death exceeds the
      respawn budget and the remaining gang also fails.
    """
    kv_daemon = None
    gang_dir = None
    if args.kv == "tcp":
        kv_daemon, addr = _start_kv_daemon(args.gang_addr)
        extra = {"MXTPU_GANG_KV": "tcp", "MXTPU_GANG_ADDR": addr,
                 "MXTPU_ELASTIC": "1"}
        sys.stderr.write(f"[launch] elastic gang KV daemon at {addr} "
                         f"(pid {kv_daemon.pid})\n")
    else:
        gang_dir = args.gang_dir or tempfile.mkdtemp(prefix="mxtpu_gang_")
        extra = {"MXTPU_GANG_DIR": gang_dir, "MXTPU_ELASTIC": "1"}
        sys.stderr.write(f"[launch] elastic gang dir: {gang_dir}\n")
    hb_timeout = float(os.environ.get("MXTPU_HEARTBEAT_TIMEOUT", 5.0))
    delay = float(os.environ.get("MXTPU_ELASTIC_RESPAWN_DELAY",
                                 1.5 * hb_timeout))
    kv_client = None
    try:
        dist = _import_distributed()
        kv_client = (dist.FileKV(gang_dir) if gang_dir is not None
                     else dist.TcpKV(addr))
    except Exception as exc:            # noqa: BLE001 — scale polling
        sys.stderr.write(f"[launch] no scale polling ({exc})\n")
    procs = {rank: _spawn_worker(cmd, rank, args.num_workers, args.port,
                                 extra)
             for rank in range(args.num_workers)}
    next_rank = args.num_workers
    respawns = 0
    failed = 0
    last_scale_poll = 0.0
    try:
        while procs:
            time.sleep(0.2)
            for rank, p in list(procs.items()):
                code = p.poll()
                if code is None:
                    continue
                del procs[rank]
                if code == 0:
                    continue                  # complete, not failed
                if not procs:
                    # nobody left to absorb the death: a real failure
                    sys.stderr.write(f"[launch] rank {rank} exited "
                                     f"rc={code} with no survivors\n")
                    failed = failed or code
                    continue
                sys.stderr.write(f"[launch] rank {rank} died rc={code}; "
                                 f"gang absorbs it "
                                 f"({len(procs)} survivors)\n")
                if respawns < args.max_restarts:
                    respawns += 1
                    time.sleep(delay)         # let the shrink commit
                    sys.stderr.write(
                        f"[launch] respawning rank {rank} "
                        f"(respawn {respawns}/{args.max_restarts})\n")
                    procs[rank] = _spawn_worker(
                        cmd, rank, args.num_workers, args.port, extra)
            now = time.monotonic()
            if kv_client is not None and procs \
                    and now - last_scale_poll >= 1.0:
                last_scale_poll = now
                try:
                    req = kv_client.get_json("scale/req")
                    if isinstance(req, dict) and \
                            int(req.get("want_world", 0)) > len(procs):
                        kv_client.delete("scale/req")
                        r = next_rank
                        next_rank += 1
                        sys.stderr.write(
                            f"[launch] scale/req want_world="
                            f"{req['want_world']}: spawning rank {r}\n")
                        procs[r] = _spawn_worker(
                            cmd, r, args.num_workers, args.port, extra)
                except Exception:   # noqa: BLE001 — KV may be failing over
                    pass
    finally:
        if kv_client is not None:
            try:
                close = getattr(kv_client, "close", None)
                if close is not None:
                    close()
            except Exception:       # noqa: BLE001
                pass
        if kv_daemon is not None and kv_daemon.poll() is None:
            kv_daemon.terminate()
            try:
                kv_daemon.wait(timeout=5.0)
            except subprocess.TimeoutExpired:
                kv_daemon.kill()
    return failed


def launch_ssh(args, cmd):
    assert args.hostfile, "--hostfile required for ssh launcher"
    with open(args.hostfile) as f:
        hosts = [h.strip() for h in f if h.strip()]
    assert len(hosts) >= args.num_workers
    coord = f"{hosts[0]}:{args.port}"
    procs = []
    for rank in range(args.num_workers):
        envs = (f"MXTPU_COORDINATOR={coord} "
                f"MXTPU_NUM_WORKERS={args.num_workers} "
                f"MXTPU_WORKER_RANK={rank}")
        remote = f"cd {os.getcwd()} && {envs} {' '.join(cmd)}"
        procs.append(subprocess.Popen(["ssh", hosts[rank], remote]))
    code = 0
    for p in procs:
        code = p.wait() or code
    return code


def main(argv=None):
    parser = argparse.ArgumentParser(
        description="Launch a distributed mxnet_tpu job")
    parser.add_argument("-n", "--num-workers", type=int, required=True)
    parser.add_argument("--launcher", choices=["local", "ssh"],
                        default="local")
    parser.add_argument("--hostfile", default=None)
    parser.add_argument("--port", type=int, default=9927)
    parser.add_argument("--max-restarts", type=int, default=0,
                        help="default mode: relaunch the full gang up to "
                             "N times after a nonzero worker exit; "
                             "--elastic: respawn up to N dead ranks")
    parser.add_argument("--grace", type=float, default=10.0,
                        help="seconds between SIGTERM and SIGKILL when "
                             "tearing down a failed gang")
    parser.add_argument("--elastic", action="store_true",
                        help="elastic supervision (local launcher): a "
                             "dead rank is absorbed by the surviving "
                             "gang and respawned individually instead "
                             "of restarting everyone")
    parser.add_argument("--gang-dir", default=None,
                        help="shared control-plane dir for --elastic "
                             "(default: a fresh temp dir)")
    parser.add_argument("--kv", choices=["file", "tcp"], default="file",
                        help="--elastic control plane: 'file' shares "
                             "--gang-dir; 'tcp' embeds the gang_kv.py "
                             "daemon (no shared filesystem)")
    parser.add_argument("--gang-addr", default=None,
                        help="HOST:PORT for --kv tcp (default "
                             "127.0.0.1:0 — a free port)")
    parser.add_argument("command", nargs=argparse.REMAINDER)
    args = parser.parse_args(argv)
    cmd = args.command
    if cmd and cmd[0] == "--":
        cmd = cmd[1:]
    if not cmd:
        parser.error("no command given")
    if args.launcher != "local":
        if args.elastic:
            parser.error("--elastic requires the local launcher")
        return launch_ssh(args, cmd)
    if args.elastic:
        return launch_elastic(args, cmd)
    return launch_local(args, cmd)


if __name__ == "__main__":
    sys.exit(main())

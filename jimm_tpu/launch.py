"""Multi-process launcher — the torchrun/mpirun counterpart for jimm_tpu.

The reference scales out via externally-launched MPI/NCCL ranks; the
TPU-native equivalent is one process per host plus `jax.distributed`
(SURVEY §2.3 "collective communication backend"). Cloud TPU pods need no
launcher at all — the TPU runtime starts one process per host and
``initialize_distributed()`` auto-detects. This covers the cases where
nothing spawns those processes for you:

- **local simulation**: N processes x M virtual CPU devices on one machine
  (the exact topology `tests/test_distributed.py` exercises),
- **manual multi-node**: run the same command on every node with its
  ``--node-rank``; node 0's address is the coordinator.

A TPU chip belongs to one process at a time, and one process drives every
chip of its host. So ``--nproc`` above 1 is for virtual CPU clusters only:
on a TPU host the second local process would fail or hang waiting for chips
the first one holds, and ``--nproc > 1`` with ``--platform tpu`` is refused.
The launcher itself never touches JAX, so its children are free to.

Usage::

    # 2 local processes x 2 virtual CPU devices each (4-device cluster)
    python -m jimm_tpu.launch --nproc 2 --platform cpu --host-devices 2 -- \
        python -m jimm_tpu train --preset siglip-base-patch16-256 ...

    # manual 2-node cluster, one process per node
    python -m jimm_tpu.launch --nnodes 2 --node-rank 0 \
        --coordinator node0:12345 -- python train.py   # on node 0
    python -m jimm_tpu.launch --nnodes 2 --node-rank 1 \
        --coordinator node0:12345 -- python train.py   # on node 1

Children receive ``JIMM_COORDINATOR`` / ``JIMM_NUM_PROCESSES`` /
``JIMM_PROCESS_ID`` (plus ``JIMM_PLATFORM`` / ``JIMM_HOST_DEVICES``
passthrough); a bare ``initialize_distributed()`` — which the CLI calls
automatically — picks them up. Child output is line-prefixed with its
global rank; the first failing child terminates the rest.
"""

from __future__ import annotations

import argparse
import os
import signal
import socket
import subprocess
import sys
import threading


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _pump(stream, rank: int, out) -> None:
    for line in iter(stream.readline, ""):
        out.write(f"[rank {rank}] {line}")
        out.flush()
    stream.close()


def main(argv: list[str] | None = None) -> int:
    p = argparse.ArgumentParser(
        prog="python -m jimm_tpu.launch",
        description="Spawn a jax.distributed process group and run CMD in "
                    "every process (everything after `--`).")
    p.add_argument("--nproc", type=int, default=1,
                   help="processes to spawn on THIS node")
    p.add_argument("--nnodes", type=int, default=1,
                   help="total nodes in the cluster (run this launcher on "
                        "each, with its --node-rank)")
    p.add_argument("--node-rank", type=int, default=0)
    p.add_argument("--coordinator", default=None,
                   help="host:port of global process 0 (required when "
                        "--nnodes > 1; defaults to 127.0.0.1:<free port>)")
    p.add_argument("--platform", default=None,
                   help="JIMM_PLATFORM for children (e.g. cpu)")
    p.add_argument("--host-devices", type=int, default=None,
                   help="virtual CPU devices per process (JIMM_HOST_DEVICES)")
    p.add_argument("--restarts", type=int, default=0,
                   help="relaunch the whole group up to N times after a "
                        "failure (preemption, crash); the command should "
                        "be resumable — e.g. include --ckpt-dir and "
                        "--resume, which cold-starts cleanly on the first "
                        "attempt")
    p.add_argument("--restart-backoff-s", type=float, default=1.0,
                   help="base of the jittered exponential backoff between "
                        "group relaunches")
    p.add_argument("cmd", nargs=argparse.REMAINDER,
                   help="command to run in every process, after `--`")
    args = p.parse_args(argv)

    cmd = args.cmd[1:] if args.cmd[:1] == ["--"] else args.cmd
    if not cmd:
        p.error("no command given (put it after `--`)")
    if args.nnodes < 1 or not 0 <= args.node_rank < args.nnodes:
        p.error(f"--node-rank {args.node_rank} outside [0, {args.nnodes})")
    if args.nnodes > 1 and not args.coordinator:
        p.error("--coordinator host:port is required with --nnodes > 1")
    if args.nproc < 1:
        p.error("--nproc must be >= 1")
    if args.nproc > 1 and args.platform == "tpu":
        p.error("--nproc > 1 with --platform tpu: a TPU host's chips belong "
                "to one process; run one process per host (--nnodes) and "
                "let it drive all local chips")
    if args.restarts < 0:
        p.error("--restarts must be >= 0")
    world = args.nnodes * args.nproc
    if world < 2:
        p.error("a 1-process world needs no launcher; run the command "
                "directly")

    from jimm_tpu.resilience import BackoffPolicy
    backoff = BackoffPolicy(base_s=args.restart_backoff_s, max_s=60.0,
                            jitter=0.5)
    import time

    rc = 0
    for attempt in range(args.restarts + 1):
        # a fresh auto-coordinator port per attempt: the previous group's
        # listener may still be in TIME_WAIT
        coordinator = args.coordinator or f"127.0.0.1:{_free_port()}"
        rc = _run_group(args, cmd, coordinator)
        if rc == 0 or rc == 130:  # success, or operator stop — don't retry
            break
        if attempt < args.restarts:
            delay = backoff.delay(attempt)
            print(f"[launch] group failed (rc {rc}); restart "
                  f"{attempt + 1}/{args.restarts} in {delay:.1f}s",
                  file=sys.stderr)
            time.sleep(delay)
    return rc


def _run_group(args, cmd: list[str], coordinator: str) -> int:
    """Spawn one process group, wait it out, and return its exit code
    (first failure wins; 130 = interrupted by the operator)."""
    world = args.nnodes * args.nproc
    procs: list[subprocess.Popen] = []
    pumps: list[threading.Thread] = []
    for local in range(args.nproc):
        rank = args.node_rank * args.nproc + local
        env = dict(os.environ,
                   JIMM_COORDINATOR=coordinator,
                   JIMM_NUM_PROCESSES=str(world),
                   JIMM_PROCESS_ID=str(rank))
        if args.platform:
            env["JIMM_PLATFORM"] = args.platform
        if args.host_devices:
            env["JIMM_HOST_DEVICES"] = str(args.host_devices)
        proc = subprocess.Popen(cmd, env=env, stdout=subprocess.PIPE,
                                stderr=subprocess.STDOUT, text=True,
                                bufsize=1)
        procs.append(proc)
        t = threading.Thread(target=_pump, args=(proc.stdout, rank,
                                                 sys.stdout), daemon=True)
        t.start()
        pumps.append(t)

    import time

    state = {"interrupted": False, "kill_at": None}

    def terminate_all(signum=None, frame=None):
        if signum is not None:
            state["interrupted"] = True
        if state["kill_at"] is None:
            # SIGTERM now; escalate to SIGKILL if anything survives 10 s
            # (a rank wedged in uninterruptible I/O or a blocking handler
            # must not hang the launcher forever)
            state["kill_at"] = time.monotonic() + 10
        for proc in procs:
            if proc.poll() is None:
                proc.terminate()

    signal.signal(signal.SIGINT, terminate_all)
    signal.signal(signal.SIGTERM, terminate_all)

    # wait for all; the first failure tears the group down (a dead rank
    # would otherwise hang the rest inside a collective forever)
    rc = 0
    pending = set(range(args.nproc))
    while pending:
        for i in sorted(pending):
            code = procs[i].poll()
            if code is None:
                continue
            pending.discard(i)
            if code and not rc:
                # subprocess reports signal deaths as -signum; shells use
                # 128+signum — keep that convention for CI legibility
                rc = 128 - code if code < 0 else code
                if not state["interrupted"]:
                    print(f"[launch] rank "
                          f"{args.node_rank * args.nproc + i} exited "
                          f"{code}; terminating the group", file=sys.stderr)
                    terminate_all()
            break
        else:
            if state["kill_at"] and time.monotonic() > state["kill_at"]:
                for proc in procs:
                    if proc.poll() is None:
                        proc.kill()
                state["kill_at"] = time.monotonic() + 10
            time.sleep(0.2)
    for t in pumps:
        t.join(timeout=5)
    if state["interrupted"] and not rc:
        return 130  # operator stop, not a rank failure (a failure that
        # preceded the interrupt keeps its code)
    return rc


if __name__ == "__main__":
    sys.exit(main())

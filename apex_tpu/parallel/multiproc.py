"""Multi-process launcher for multi-host (multi-controller) JAX runs.

≡ apex.parallel.multiproc (apex/parallel/multiproc.py): the reference
spawns `nproc_per_node` copies of a training script with RANK/WORLD_SIZE
env vars for `torch.distributed`.  The TPU-native analogue launches N
controller processes wired to a `jax.distributed` coordinator; on CPU it
additionally forces the emulated-device platform so sharding code paths
run without TPU hardware (the harness gap called out in SURVEY.md §4).

Usage:
    python -m apex_tpu.parallel.multiproc --nproc 4 train.py --arg ...

Each child gets:
    APEX_TPU_COORDINATOR   host:port of the jax.distributed coordinator
    APEX_TPU_NUM_PROCESSES total process count
    APEX_TPU_PROCESS_ID    this process's id
and (CPU emulation) JAX_PLATFORMS=cpu plus
--xla_force_host_platform_device_count so every process sees
`devices_per_proc` local devices.  `init_from_env()` is the child-side
hook that calls `jax.distributed.initialize` from those variables.

Failure semantics (ISSUE 11): children are POLLED concurrently — a
child that dies first no longer leaves its siblings hung on a
collective until some outer CI timeout eats the budget.  The first
nonzero exit is propagated as the launcher's return code; surviving
children get `--grace` seconds to finish on their own (the fleet
probe's survivors must be OBSERVABLE committing-or-refusing — grace 0,
the default, terminates them immediately), then SIGTERM → SIGKILL.
`--timeout` bounds the whole fleet: a hung run fails loudly instead of
hanging CI.
"""

from __future__ import annotations

import argparse
import os
import subprocess
import sys
import time

__all__ = ["main", "init_from_env", "wait_fleet"]


def init_from_env():
    """Child-side: initialize jax.distributed from launcher env vars.

    ≡ the `torch.distributed.init_process_group(init_method='env://')`
    call the reference's spawned scripts perform.  No-op when the
    launcher variables are absent (single-process run).
    """
    coord = os.environ.get("APEX_TPU_COORDINATOR")
    if not coord:
        return False
    import jax

    devs = int(os.environ.get("APEX_TPU_DEVICES_PER_PROC", "0"))
    if devs > 0:
        jax.config.update("jax_platforms", "cpu")
        jax.config.update("jax_num_cpu_devices", devs)
    jax.distributed.initialize(
        coordinator_address=coord,
        num_processes=int(os.environ["APEX_TPU_NUM_PROCESSES"]),
        process_id=int(os.environ["APEX_TPU_PROCESS_ID"]),
    )
    return True


def wait_fleet(procs, *, timeout=None, grace=0.0, poll=0.05,
               term_wait=5.0):
    """Poll `procs` (subprocess.Popen) until all exit, any one fails,
    or `timeout` elapses.  Returns the fleet's return code: 0 when
    every child exited 0; the FIRST nonzero exit otherwise; 124 on
    timeout (the `timeout(1)` convention).

    On first failure the survivors get `grace` seconds to finish on
    their own — a checkpoint barrier refusing cleanly IS the behavior
    under test when a sibling dies — then are terminated (SIGTERM,
    escalating to SIGKILL after `term_wait`).  On timeout everything
    is terminated immediately.
    """
    deadline = None if timeout is None else time.monotonic() + timeout

    def _alive():
        return [p for p in procs if p.poll() is None]

    def _terminate(alive):
        for p in alive:
            try:
                p.terminate()
            except OSError:  # pragma: no cover — already gone
                pass
        t_kill = time.monotonic() + term_wait
        for p in alive:
            while p.poll() is None and time.monotonic() < t_kill:
                time.sleep(poll)
            if p.poll() is None:
                try:
                    p.kill()
                except OSError:  # pragma: no cover
                    pass
                p.wait()

    rc = 0
    grace_deadline = None
    while True:
        alive = _alive()
        if rc == 0:
            for p in procs:
                r = p.poll()
                if r:  # first failure wins; record + start the grace
                    rc = r
                    grace_deadline = time.monotonic() + grace
                    break
        if not alive:
            return rc
        now = time.monotonic()
        if deadline is not None and now >= deadline:
            sys.stderr.write(
                f"multiproc: fleet timeout after {timeout}s — "
                f"terminating {len(alive)} hung child(ren)\n")
            _terminate(alive)
            return rc or 124
        if grace_deadline is not None and now >= grace_deadline:
            _terminate(_alive())
            return rc
        time.sleep(poll)


def main(argv=None):
    parser = argparse.ArgumentParser(
        description="apex_tpu multi-process launcher "
                    "(≡ apex/parallel/multiproc.py)")
    parser.add_argument("--nproc", type=int, default=2,
                        help="number of controller processes to spawn")
    parser.add_argument("--coordinator", default="127.0.0.1:12355",
                        help="jax.distributed coordinator host:port")
    parser.add_argument("--devices-per-proc", type=int, default=0,
                        help=">0: force CPU emulation with this many "
                             "virtual devices per process")
    parser.add_argument("--timeout", type=float, default=None,
                        help="kill the whole fleet after this many "
                             "seconds (exit 124) — a hung fleet fails "
                             "CI instead of eating its budget")
    parser.add_argument("--grace", type=float, default=0.0,
                        help="after the first child failure, let "
                             "survivors run this many seconds before "
                             "terminating them (default 0: immediate)")
    parser.add_argument("script", help="training script to run")
    parser.add_argument("script_args", nargs=argparse.REMAINDER)
    args = parser.parse_args(argv)

    procs = []
    for pid in range(args.nproc):
        env = dict(os.environ)
        env["APEX_TPU_COORDINATOR"] = args.coordinator
        env["APEX_TPU_NUM_PROCESSES"] = str(args.nproc)
        env["APEX_TPU_PROCESS_ID"] = str(pid)
        if args.devices_per_proc > 0:
            env["APEX_TPU_DEVICES_PER_PROC"] = str(args.devices_per_proc)
            env["JAX_PLATFORMS"] = "cpu"
            flags = env.get("XLA_FLAGS", "")
            env["XLA_FLAGS"] = (
                f"{flags} --xla_force_host_platform_device_count="
                f"{args.devices_per_proc}").strip()
        cmd = [sys.executable, args.script] + args.script_args
        procs.append(subprocess.Popen(cmd, env=env))

    # Mirror the reference's behavior of surfacing a child failure —
    # but poll ALL children: the old in-order wait left siblings hung
    # on a dead rank's collective until an outer timeout fired.
    return wait_fleet(procs, timeout=args.timeout, grace=args.grace)


if __name__ == "__main__":
    sys.exit(main())

"""Local N-process spawner: the ``mp.spawn`` launch mode, TPU-framework style.

The reference's primary launch path forks one worker per GPU from a single
command (``demo_spawn`` -> ``mp.spawn(run_spawn, nprocs=ngpus)``,
``/root/reference/multi_proc_single_gpu.py:273-285``), with rank = spawned
process id and a loopback TCP rendezvous (``:326``). On TPU the runtime is
one process per *host*, so the faithful analog is spawning N local
*host* processes — each owning one CPU device — that rendezvous through
``jax.distributed.initialize`` on a free loopback port. That is exactly the
world a real N-host pod presents, minus the hardware: every multi-host code
path (``make_array_from_process_local_data``, disjoint per-host sampler
shards, cross-process metric psums, process-0-only checkpoint writes, the
sharded ``.ckpt`` layout) executes for real.

Children are forced onto the CPU backend: N processes cannot share one TPU
chip (the TPU rule is one process per host — on real pods no spawner is
needed at all), so ``--spawn`` is the local-simulation launcher, the moral
equivalent of running the reference on a machine with N GPUs.

Unlike the reference there is no second, comment-toggled launch mode
(``:353-359``): ``--spawn N`` composes with every other flag, and explicit
``--coordinator/--process-id`` remain available for real multi-host runs.
"""

from __future__ import annotations

import os
import re
import socket
import subprocess
import sys
import tempfile
from typing import List, Optional, Sequence


def free_port() -> int:
    """A free loopback port for the coordinator (the reference hard-codes
    ``tcp://127.0.0.1:23456``, ``:326``; a bound-then-released port avoids
    collisions between concurrent runs)."""
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def strip_flags(argv: Sequence[str], flags: dict) -> List[str]:
    """Remove launcher-consumed flags from an argv copy.

    ``flags`` maps flag name -> number of value tokens to drop with it
    (``=``-joined forms are always one token). The ONE argv-stripping
    loop for every spawner-side flag — ``--spawn`` here, the elastic
    supervisor's ``--elastic``/``--min-world``/``--resume`` rewrites
    (``runtime/elastic.py``) — so a flag-syntax fix lands once."""
    out: List[str] = []
    skip = 0
    for a in argv:
        if skip:
            skip -= 1
            continue
        if a in flags:
            skip = flags[a]
            continue
        if any(a.startswith(flag + "=") for flag in flags):
            continue
        out.append(a)
    return out


def strip_spawn_flag(argv: Sequence[str]) -> List[str]:
    """Remove ``--spawn N`` / ``--spawn=N`` from an argv copy."""
    return strip_flags(argv, {"--spawn": 1})


def announce_cpu_simulation(nprocs: int) -> None:
    """A spawned world is a CPU simulation of a multi-host pod; say so on
    stdout when it starts, so nothing it prints is read as a chip's."""
    print(f"spawn: CPU simulation of a {nprocs}-host world — {nprocs} "
          f"local processes with one CPU device each (JAX_PLATFORMS=cpu); "
          f"no accelerator is used", flush=True)


def _child_env() -> dict:
    """Environment for one spawned host process: CPU backend, exactly ONE
    local device (any ``xla_force_host_platform_device_count`` from the
    caller — e.g. the test suite's 8-device conftest — is stripped so the
    N-process world has N global devices, like N one-chip hosts)."""
    env = dict(os.environ)
    flags = env.get("XLA_FLAGS", "")
    flags = re.sub(
        r"--xla_force_host_platform_device_count=\d+", "", flags
    ).strip()
    env["XLA_FLAGS"] = flags
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONUNBUFFERED"] = "1"
    repo = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
    return env


def spawn_local(
    nprocs: int,
    argv: Sequence[str],
    *,
    timeout: Optional[float] = None,
) -> int:
    """Fork ``nprocs`` local host processes running the CLI; return max rc.

    Rank 0's output streams to this terminal live (the reference prints
    from every rank, ``:238-242``; here non-zero ranks are mostly silent by
    design — ``log0`` — so their output is captured to temp files and only
    replayed on failure). Rank assignment is spawn order, the reference's
    ``run_spawn(proc_id)`` convention (``:273-276``).
    """
    if nprocs < 2:
        raise ValueError(f"--spawn needs >= 2 processes, got {nprocs}")
    child_argv = strip_spawn_flag(argv)
    port = free_port()
    env = _child_env()
    announce_cpu_simulation(nprocs)

    procs = []
    logs = []
    for rank in range(nprocs):
        cmd = [
            sys.executable, "-m", "pytorch_distributed_mnist_tpu",
            *child_argv,
            "--coordinator", f"127.0.0.1:{port}",
            "--num-processes", str(nprocs),
            "--process-id", str(rank),
        ]
        if rank == 0:
            procs.append(subprocess.Popen(cmd, env=env))
            logs.append(None)
        else:
            # Temp files, not pipes: a filled pipe buffer would deadlock a
            # chatty child against a parent that only reads at the end.
            log = tempfile.TemporaryFile(mode="w+")
            procs.append(subprocess.Popen(
                cmd, env=env, stdout=log, stderr=subprocess.STDOUT))
            logs.append(log)

    rcs = []
    try:
        for p in procs:
            rcs.append(p.wait(timeout=timeout))
    except subprocess.TimeoutExpired:
        for p in procs:
            if p.poll() is None:
                p.kill()
        raise
    finally:
        for rank, (rc_p, log) in enumerate(zip(procs, logs)):
            if log is None:
                continue
            if rc_p.returncode not in (0, None):
                log.seek(0)
                tail = log.read()[-4000:]
                print(f"--- spawned process {rank} failed "
                      f"(rc={rc_p.returncode}) ---\n{tail}",
                      file=sys.stderr)
            log.close()
    # A signal-killed child has a NEGATIVE returncode; max() over mixed
    # signs could report 0 despite a crashed rank. Any nonzero rc is a
    # failed run: surface the first one (signals map to the shell's 128+N).
    bad = [rc for rc in rcs if rc != 0]
    if not bad:
        return 0
    return bad[0] if bad[0] > 0 else 128 - bad[0]

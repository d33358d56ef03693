"""DistributedTest-style N-process harness (VERDICT r4 next #5).

The reference forks arbitrary world sizes per test
(``tests/unit/common.py:66,244`` ``DistributedTest``); this is the JAX
analog: :func:`launch` forks ``world_size`` fresh Python processes (a
new process per rank is mandatory — each needs its own JAX backend),
gives them OpenMPI-style identity env vars (so ``comm.mpi_discovery``
— not the harness — resolves rank/size, as under ``mpirun``) and a
local TCP coordination service, then runs a named BODY function in
each child and collects outputs.

Bodies live in importable modules (``tests/unit/dist_bodies.py``) and
are referenced as ``"package.module:function"``; they read their own
rank/world from the initialized backend. This file doubles as the child
entrypoint (``python dist_harness.py pkg.mod:fn``).
"""

import os
import socket
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def launch(fn_ref: str, world_size: int, devices_per_proc: int = 2,
           timeout: int = 300):
    """Run ``fn_ref`` in ``world_size`` rendezvoused processes.

    Returns the per-rank stdout list; raises AssertionError with the
    failing rank's output on any non-zero exit. Each body should print
    ``DIST-BODY-OK rank=<r>`` on success (asserted here) so a child that
    silently exits early still fails the test.
    """
    port = _free_port()
    env_base = dict(os.environ)
    env_base["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={devices_per_proc}")
    env_base.pop("RANK", None)
    env_base.pop("WORLD_SIZE", None)
    pypath = env_base.get("PYTHONPATH", "")
    env_base["PYTHONPATH"] = REPO + os.pathsep + pypath if pypath else REPO
    procs = []
    for rank in range(world_size):
        env = dict(env_base)
        env["OMPI_COMM_WORLD_RANK"] = str(rank)
        env["OMPI_COMM_WORLD_SIZE"] = str(world_size)
        env["OMPI_COMM_WORLD_LOCAL_RANK"] = str(rank)
        env["MASTER_ADDR"] = "127.0.0.1"
        env["MASTER_PORT"] = str(port)
        procs.append(subprocess.Popen(
            [sys.executable, "-u", os.path.abspath(__file__), fn_ref],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            text=True))
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=timeout)
            outs.append(out)
    except subprocess.TimeoutExpired:
        for p in procs:
            p.kill()
        # ranks that already communicate()d have CLOSED stdout pipes —
        # reuse their collected output; only drain the hung ones
        partial = []
        for i, p in enumerate(procs):
            if i < len(outs):
                partial.append(outs[i])
                continue
            try:
                partial.append(p.communicate(timeout=10)[0] or "")
            except Exception:
                partial.append("<no output: killed while hung>")
        raise AssertionError(
            f"{fn_ref} hung at world_size={world_size}:\n"
            + "\n".join(f"--- rank {i}:\n{o}"
                        for i, o in enumerate(partial)))
    for rank, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, (
            f"{fn_ref} rank {rank}/{world_size} failed:\n{out}")
        assert f"DIST-BODY-OK rank={rank}" in out, (
            f"{fn_ref} rank {rank} exited early:\n{out}")
    return outs


def _child_main(fn_ref: str):
    import importlib

    import jax

    jax.config.update("jax_platforms", "cpu")

    import deepspeed_tpu.comm as dist

    backend = dist.init_distributed()
    assert backend is not None
    rank = jax.process_index()
    assert rank == int(os.environ["OMPI_COMM_WORLD_RANK"]), (
        "mpi_discovery must map the scheduler rank onto the JAX process id")
    mod_name, fn_name = fn_ref.split(":")
    fn = getattr(importlib.import_module(mod_name), fn_name)
    fn()
    dist.barrier()
    print(f"DIST-BODY-OK rank={rank}", flush=True)


if __name__ == "__main__":
    _child_main(sys.argv[1])

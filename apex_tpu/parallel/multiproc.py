"""Multi-process launcher for CPU simulation and multi-host bring-up.

Reference: ``apex/parallel/multiproc.py:12-35`` — spawn one training
process per GPU with ``--rank``/``--world-size`` appended.

On one TPU host this launcher is NOT how the chips are used: one
process drives all local chips (SPMD over a mesh), a chip belongs to one
process at a time, and a second process that asks for it fails or
hangs. The launcher is for multi-process *CPU* simulation (each child
gets its own virtual host devices) and for standing in for the
per-host launch of a multi-host job: it spawns ``world_size`` processes
with the coordinator env set so ``jax.distributed.initialize`` connects
them. ``main`` calls nothing in jax, so the parent initialises no
backend and never holds a device its children need; ``--world-size``
defaults to 1 and is otherwise stated by the caller.

Usage: ``python -m apex_tpu.parallel.multiproc [--world-size N] script.py args...``
"""

from __future__ import annotations

import os
import subprocess
import sys


def init_distributed(coordinator_address: str | None = None,
                     num_processes: int | None = None,
                     process_id: int | None = None):
    """Connect this process to the JAX distributed runtime.

    Reads the env contract this launcher sets (``JAX_COORDINATOR_ADDRESS``,
    ``JAX_NUM_PROCESSES``, ``JAX_PROCESS_ID``) unless given explicitly —
    the multi-host analog of the reference's ``--rank``/``--world-size``
    plumbing into ``torch.distributed.init_process_group``
    (``apex/parallel/multiproc.py:12-35``). On real TPU pods the args are
    auto-detected and this reduces to ``jax.distributed.initialize()``.

    After this, ``jax.devices()`` spans all hosts;
    ``parallel_state.initialize_model_parallel`` then builds the global
    mesh with the data axis outermost, so DP crosses hosts (DCN) while
    tp/pp/cp ride intra-host ICI.
    """
    import jax

    coordinator_address = coordinator_address or os.environ.get(
        "JAX_COORDINATOR_ADDRESS")
    if num_processes is None and os.environ.get("JAX_NUM_PROCESSES"):
        num_processes = int(os.environ["JAX_NUM_PROCESSES"])
    if process_id is None and os.environ.get("JAX_PROCESS_ID"):
        process_id = int(os.environ["JAX_PROCESS_ID"])
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id)
    # rank-tag any attached recorder so its JSONL shard self-identifies
    # (monitor.merge reads process_index/process_count from the header)
    from apex_tpu.monitor import _state as _monitor_state
    rec = _monitor_state.recorder
    if rec is not None:
        rec.meta.setdefault("process_index", jax.process_index())
        rec.meta.setdefault("process_count", jax.process_count())
        rec.gauge("dist/process_index", jax.process_index())


def main(argv=None):
    argv = list(sys.argv[1:] if argv is None else argv)
    world_size = 1
    if argv and argv[0] == "--world-size":
        world_size = int(argv[1])
        argv = argv[2:]
    if not argv:
        print(__doc__)
        return 1

    port = int(os.environ.get("APEX_TPU_COORD_PORT", "12355"))
    procs = []
    for rank in range(world_size):
        env = dict(os.environ)
        env.update({
            "JAX_COORDINATOR_ADDRESS": f"127.0.0.1:{port}",
            "JAX_NUM_PROCESSES": str(world_size),
            "JAX_PROCESS_ID": str(rank),
        })
        cmd = [sys.executable] + argv + ["--rank", str(rank),
                                         "--world-size", str(world_size)]
        procs.append(subprocess.Popen(cmd, env=env))
    rc = 0
    for p in procs:
        rc |= p.wait()
    return rc


if __name__ == "__main__":
    sys.exit(main())

"""paddle.distributed surface (reference: python/paddle/distributed/__init__.py).

TPU-native design (SURVEY §7): one ND device mesh + GSPMD shardings replace
process groups; explicit collectives run via shard_map; rendezvous via JAX's
coordination service.
"""
from .env import (ParallelEnv, get_rank, get_world_size, init_parallel_env,  # noqa: F401
                  is_initialized)
from .parallel import DataParallel  # noqa: F401

# filled in as the distributed stack lands this round:
from .auto_parallel.api import (ProcessMesh, shard_tensor, reshard, shard_layer,  # noqa: F401
                                dtensor_from_fn, unshard_dtensor)
from .auto_parallel.placement import (Placement, Replicate, Shard, Partial)  # noqa: F401
from .auto_parallel import (parallelize, to_distributed, Engine, Strategy,  # noqa: F401
                            ColWiseParallel, RowWiseParallel,
                            SequenceParallelBegin, SequenceParallelEnd,
                            SequenceParallelEnable)
from .watchdog import CommTaskManager  # noqa: F401
from .collective import (all_reduce, all_gather, all_gather_object, reduce,  # noqa: F401
                         broadcast, scatter, all_to_all, reduce_scatter,
                         send, recv, barrier, new_group, get_group, ReduceOp,
                         split_group, broadcast_object_list, alltoall,
                         all_to_all_single, gather, gather_object,
                         scatter_object_list, isend, irecv, wait, P2POp,
                         batch_isend_irecv, destroy_process_group)
from . import mesh_utils  # noqa: F401
from .mesh_utils import create_mesh, create_hybrid_mesh  # noqa: F401
from . import fleet  # noqa: F401
from . import checkpoint  # noqa: F401
from . import ps_sparse  # noqa: F401  (host-resident sparse embedding PS)


def spawn(func, args=(), nprocs=-1, join=True, daemon=False, **options):
    """reference: distributed/spawn.py:463 — single-node multiprocess launch.
    Children can call init_parallel_env(): a coordinator address on a free
    port is provisioned here."""
    import multiprocessing as mp
    import socket
    from paddle_tpu.core.hermetic import local_chip_count, one_chip_env
    if nprocs == -1:
        # counted from /dev, never through jax: a parent that initialized the
        # backend would hold the very chips its children need
        nprocs = local_chip_count() or 1
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    ctx = mp.get_context("spawn")
    procs = []
    for rank in range(nprocs):
        env = {"PADDLE_TRAINER_ID": str(rank),
               "PADDLE_TRAINERS_NUM": str(nprocs),
               "PADDLE_LOCAL_RANK": str(rank),
               "PADDLE_MASTER": f"127.0.0.1:{port}",
               "MASTER_ADDR": "127.0.0.1", "MASTER_PORT": str(port)}
        if nprocs > 1:      # sibling workers each open their own chip
            env = one_chip_env(rank, base=env)
        p = ctx.Process(target=_spawn_entry, args=(func, args, env),
                        daemon=daemon)
        p.start()
        procs.append(p)
    if join:
        for p in procs:
            p.join()
        bad = [p.exitcode for p in procs if p.exitcode != 0]
        if bad:
            raise RuntimeError(f"spawn: worker(s) failed with exit codes {bad}")
    return procs


def _spawn_entry(func, args, env):
    import os
    os.environ.update(env)
    func(*args)

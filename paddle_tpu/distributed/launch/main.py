# graftlint: disable-file=no-adhoc-telemetry  (CLI front-end: stdout is the UI)
"""Multi-process launcher (reference: python/paddle/distributed/launch/main.py:23
+ controllers/collective.py). Spawns one worker process per device/slot, wires
the rendezvous env (coordinator address + rank/world), tees per-rank logs, and
supervises: any worker failure tears the job down (or restarts it when
--max_restarts > 0 — the elastic manager's restart loop,
reference fleet/elastic/manager.py:125).

Usage:
    python -m paddle_tpu.distributed.launch --nproc_per_node=2 train.py ...
"""
from __future__ import annotations

import argparse
import os
import signal
import subprocess
import sys
import time

from paddle_tpu.core.hermetic import (cpu_child_env, local_chip_count,
                                      one_chip_env)


def _parse(argv=None):
    p = argparse.ArgumentParser(prog="paddle_tpu.distributed.launch")
    p.add_argument("--nproc_per_node", "--nprocs", type=int, default=None,
                   help="workers on this node (default: one per local TPU "
                        "chip, counted from /dev without opening any; 1 "
                        "with --backend cpu)")
    p.add_argument("--nnodes", type=int, default=1)
    p.add_argument("--node_rank", type=int,
                   default=int(os.environ.get("PADDLE_NODE_RANK", "0")))
    p.add_argument("--master", default=os.environ.get("PADDLE_MASTER",
                                                      "127.0.0.1:8476"),
                   help="coordinator host:port (rank-0 node)")
    p.add_argument("--log_dir", default="log")
    p.add_argument("--max_restarts", type=int, default=0,
                   help="elastic: restart the whole local group this many "
                        "times on worker failure")
    p.add_argument("--backend", default=None,
                   help="set JAX_PLATFORMS for workers (e.g. cpu)")
    p.add_argument("script")
    p.add_argument("script_args", nargs=argparse.REMAINDER)
    return p.parse_args(argv)


def _worker_env(args, local_rank):
    world = args.nnodes * args.nproc_per_node
    rank = args.node_rank * args.nproc_per_node + local_rank
    host, port = (args.master.split(":") + ["8476"])[:2]
    if args.backend == "cpu":
        env = cpu_child_env()
    elif args.nproc_per_node > 1:
        # a chip belongs to one process: sibling workers each open their own
        env = one_chip_env(local_rank)
    else:
        env = dict(os.environ)      # a lone worker drives every local chip
    env.update({
        "PADDLE_TRAINER_ID": str(rank),
        "PADDLE_TRAINERS_NUM": str(world),
        "PADDLE_LOCAL_RANK": str(local_rank),
        "PADDLE_MASTER": args.master,
        "MASTER_ADDR": host,
        "MASTER_PORT": port,
        "PADDLE_CURRENT_ENDPOINT": f"{host}:{int(port) + 1 + rank}",
        "PADDLE_TRAINER_ENDPOINTS": ",".join(
            f"{host}:{int(port) + 1 + r}" for r in range(world)),
        "FLAGS_selected_tpus": str(local_rank),
    })
    if args.backend:
        env["JAX_PLATFORMS"] = args.backend
    return env


def _spawn_all(args):
    os.makedirs(args.log_dir, exist_ok=True)
    procs, logs = [], []
    for lr in range(args.nproc_per_node):
        rank = args.node_rank * args.nproc_per_node + lr
        logf = open(os.path.join(args.log_dir, f"workerlog.{rank}"), "ab")
        cmd = [sys.executable, "-u", args.script] + args.script_args
        p = subprocess.Popen(cmd, env=_worker_env(args, lr),
                             stdout=logf, stderr=subprocess.STDOUT)
        procs.append(p)
        logs.append(logf)
    return procs, logs


def _supervise(procs):
    """Wait for all; on first failure kill the rest. Returns worst rc."""
    pending = {p.pid: p for p in procs}
    rc = 0
    while pending:
        time.sleep(0.2)
        for pid, p in list(pending.items()):
            r = p.poll()
            if r is None:
                continue
            del pending[pid]
            if r != 0:
                rc = rc or r
                for q in pending.values():
                    try:
                        q.send_signal(signal.SIGTERM)
                    except OSError:
                        pass
    return rc


def launch(argv=None):
    args = _parse(argv)
    if args.nproc_per_node is None:
        args.nproc_per_node = 1 if args.backend == "cpu" \
            else local_chip_count()
        if args.nproc_per_node == 0:
            print("launch: found no TPU chip under /dev (accel*, vfio/*) to "
                  "size the job by; pass --nproc_per_node, or --backend cpu.",
                  file=sys.stderr)
            return 2
    attempt = 0
    while True:
        procs, logs = _spawn_all(args)
        rc = _supervise(procs)
        for f in logs:
            f.close()
        if rc == 0:
            return 0
        if attempt >= args.max_restarts:
            print(f"launch: workers failed (rc={rc}) after "
                  f"{attempt + 1} attempt(s); logs in {args.log_dir}/",
                  file=sys.stderr)
            return rc
        attempt += 1
        print(f"launch: worker failure (rc={rc}); elastic restart "
              f"{attempt}/{args.max_restarts}", file=sys.stderr)


if __name__ == "__main__":
    sys.exit(launch())

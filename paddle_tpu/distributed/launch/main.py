"""Launcher implementation (reference: launch/main.py:21 + controllers/)."""

from __future__ import annotations

import argparse
import os
import signal
import subprocess
import sys
import time


def _parse_args(argv=None):
    p = argparse.ArgumentParser(
        prog="python -m paddle_tpu.distributed.launch",
        description="launch a (multi-process) training job",
    )
    p.add_argument("--nnodes", type=str, default="1",
                   help="number of nodes (or range lo:hi for elastic)")
    p.add_argument("--nproc_per_node", type=int, default=1,
                   help="processes per node (CPU testing; on TPU keep 1 "
                        "process per host and let jax own all local chips)")
    p.add_argument("--master", type=str, default=None,
                   help="coordinator endpoint ip:port (jax.distributed)")
    p.add_argument("--rank", type=int,
                   default=int(os.environ.get("PADDLE_NODE_RANK", 0)),
                   help="node rank")
    p.add_argument("--job_id", type=str, default="default")
    p.add_argument("--log_dir", type=str, default=None)
    p.add_argument("--devices", "--gpus", type=str, default=None,
                   help="visible device ids")
    p.add_argument("training_script", type=str)
    p.add_argument("training_script_args", nargs=argparse.REMAINDER)
    return p.parse_args(argv)


def _child_env(args, local_rank: int, world_size: int, global_rank: int,
               coordinator: str = None):
    env = dict(os.environ)
    env.update({
        "PADDLE_TRAINER_ID": str(global_rank),
        "PADDLE_TRAINERS_NUM": str(world_size),
        "PADDLE_LOCAL_RANK": str(local_rank),
        "PADDLE_NNODES": str(args.nnodes),
        "PADDLE_JOB_ID": args.job_id,
        "PADDLE_RANK_IN_NODE": str(local_rank),
    })
    if args.master:
        env["PADDLE_MASTER"] = args.master
        env["JAX_COORDINATOR_ADDRESS"] = coordinator or args.master
        env["JAX_PROCESS_ID"] = str(global_rank)
        env["JAX_NUM_PROCESSES"] = str(world_size)
    if args.nproc_per_node > 1:
        # several processes on one host are CPU-ONLY: a TPU belongs to one
        # process at a time, so the chip path is one process per host (the
        # in-place exec in launch()) driving all of that host's chips
        env["JAX_PLATFORMS"] = "cpu"
    return env


def _rendezvous_nodes(args, nnodes: int):
    """Multi-node rendezvous in the LAUNCHER (reference:
    launch/controllers/master.py — the master process's KV service), so
    trainer processes are born with the coordination env already set and
    jax.distributed can initialize before any backend use.

    Node 0's launcher hosts the TCPStore at ``--master`` and publishes a
    fresh coordinator endpoint (same host, free port) that node 0's
    TRAINER will bind at jax.distributed.initialize; every launcher
    registers its node and blocks until the cluster is complete. Returns
    (store, coordinator) — the store must outlive the job (trainers use it
    for app-level barriers via PADDLE_MASTER)."""
    import socket

    from paddle_tpu.distributed.store import TCPStore

    host, port = args.master.rsplit(":", 1)
    is_master = args.rank == 0
    store = TCPStore(host, int(port), is_master=is_master,
                     world_size=nnodes, timeout=300)
    if is_master:
        # bind-close-publish (the torchrun dance): a tiny window exists in
        # which another process could steal the freed port before node 0's
        # trainer binds the coordinator there; in-launcher elastic restarts
        # reuse the address (gRPC rebinds with SO_REUSEADDR), while a full
        # multi-node relaunch goes through a fresh rendezvous/port
        s = socket.socket()
        s.bind((host, 0))
        coord = f"{host}:{s.getsockname()[1]}"
        s.close()
        store.set("rdzv/coordinator", coord)
    store.set(f"rdzv/node{args.rank}", "up")
    store.wait([f"rdzv/node{r}" for r in range(nnodes)])
    coord = store.get("rdzv/coordinator").decode()
    return store, coord


def launch(args=None):
    args = args or _parse_args()
    try:
        nnodes = int(str(args.nnodes).split(":")[0])
    except ValueError:
        nnodes = 1
    world = nnodes * args.nproc_per_node

    # multi-node: rendezvous in the launcher, then ALWAYS spawn children
    # (exec-in-place would initialize this process's backend before the
    # trainer's jax.distributed bring-up). The store must stay referenced:
    # node 0's launcher hosts it for the trainers' app-level barriers.
    rdzv_store = coordinator = None
    if args.master and nnodes > 1:
        rdzv_store, coordinator = _rendezvous_nodes(args, nnodes)

    if args.nproc_per_node == 1 and rdzv_store is None:
        # single proc per host: exec in-place (the TPU path — this launcher
        # has not initialised JAX, so the trainer is the one process that
        # takes the host's chips)
        env = _child_env(args, 0, world, args.rank)
        os.environ.update(env)
        sys.argv = [args.training_script] + list(args.training_script_args)
        with open(args.training_script) as f:
            code = compile(f.read(), args.training_script, "exec")
        globs = {"__name__": "__main__", "__file__": args.training_script}
        exec(code, globs)
        return 0

    log_dir = args.log_dir
    if log_dir:
        os.makedirs(log_dir, exist_ok=True)

    def _spawn(world_size, attempt):
        procs = []
        for lr in range(args.nproc_per_node):
            grank = args.rank * args.nproc_per_node + lr
            env = _child_env(args, lr, world_size, grank, coordinator)
            stdout = (open(os.path.join(
                log_dir, f"worker.{grank}.log"
                if attempt == 0 else f"worker.{grank}.r{attempt}.log"), "w")
                if log_dir else None)
            procs.append(subprocess.Popen(
                [sys.executable, args.training_script]
                + args.training_script_args,
                env=env, stdout=stdout,
                stderr=subprocess.STDOUT if stdout else None,
            ))
        return procs

    procs = _spawn(world, 0)

    def _kill(*_):
        for p in procs:
            if p.poll() is None:
                p.terminate()

    signal.signal(signal.SIGTERM, _kill)
    # elastic supervision (reference: launch controllers + ElasticManager
    # exit-code protocol, fleet/elastic/manager.py:32): a worker exiting
    # with ELASTIC_EXIT_CODE asks for a relaunch. The supervisor POLLS so
    # one worker stuck in a collective cannot block the requested relaunch
    # (it gets terminated); the new world size comes from the world-file a
    # departing worker writes (PADDLE_ELASTIC_WORLD_FILE), since membership
    # lives in the trainers' store, not the launcher.
    from paddle_tpu.distributed.fleet.elastic import ELASTIC_EXIT_CODE

    elastic = bool(os.environ.get("PADDLE_ELASTIC_NP"))
    world_file = os.environ.get("PADDLE_ELASTIC_WORLD_FILE")
    max_restarts = int(os.environ.get("PADDLE_ELASTIC_MAX_RESTARTS", "3"))
    attempt = 0
    rc = 0
    try:
        while True:
            want_restart = False
            while True:
                rcs = [p.poll() for p in procs]
                if elastic and any(r == ELASTIC_EXIT_CODE for r in rcs
                                   if r is not None):
                    want_restart = True
                    break
                if all(r is not None for r in rcs):
                    break
                time.sleep(0.2)
            if want_restart and attempt < max_restarts:
                attempt += 1
                _kill()
                for p in procs:
                    try:
                        p.wait(timeout=30)
                    except subprocess.TimeoutExpired:
                        p.kill()
                if world_file and os.path.exists(world_file):
                    try:
                        world = int(open(world_file).read().strip())
                    except ValueError:
                        pass
                procs = _spawn(world, attempt)
                continue
            rcs = [p.wait() for p in procs]
            rc = next((r for r in rcs if r), 0)
            break
    except KeyboardInterrupt:
        _kill()
        rc = 1
    return rc


def main():
    sys.exit(launch(_parse_args()))


if __name__ == "__main__":
    main()

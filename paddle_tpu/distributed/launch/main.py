"""Launcher implementation: pod build, watcher, elastic restart.

Reference call path: launch/main.py -> CollectiveController.build_pod
(controllers/collective.py:37: per-rank env assembly) -> Watcher monitoring
(controllers/watcher.py) -> restart/elastic logic (collective.py:254
CollectiveElasticController; fleet/elastic/manager.py). The heavy pieces the
reference needs (etcd membership, gloo barriers) collapse onto the native
TCPStore: nodes register under /nodes/<rank>, barrier, and watch a restart
epoch counter.
"""
from __future__ import annotations

import argparse
import os
import signal
import subprocess
import sys
import time


def _parse_args(argv=None):
    parser = argparse.ArgumentParser("paddle_tpu.distributed.launch")
    parser.add_argument("--nnodes", type=str, default="1",
                        help="node count or range 'N' / 'N:M' (elastic)")
    parser.add_argument("--nproc_per_node", type=int, default=1,
                        help="worker processes on this node (TPU: usually 1 "
                             "process owning all local chips)")
    parser.add_argument("--master", type=str, default=None,
                        help="rendezvous endpoint ip:port (rank-0 node)")
    parser.add_argument("--rank", type=int, default=-1,
                        help="node rank; -1 = from env PADDLE_NODE_RANK or 0")
    parser.add_argument("--job_id", type=str, default="default")
    parser.add_argument("--log_dir", type=str, default="log")
    parser.add_argument("--max_restart", type=int, default=3)
    parser.add_argument("--elastic_level", type=int, default=-1)
    parser.add_argument("--elastic_timeout", type=int, default=30)
    parser.add_argument("--devices", type=str, default=None)
    parser.add_argument("--auto_tuner_json", type=str, default=None,
                        help="auto-tuner mode: JSON config describing the "
                             "search (model dims, max trials, metric); each "
                             "candidate runs the training script as one "
                             "trial (reference: launch --auto_tuner_json)")
    parser.add_argument("training_script", type=str)
    parser.add_argument("training_script_args", nargs=argparse.REMAINDER)
    return parser.parse_args(argv)


def _refuse_shared_chips(nproc_per_node: int) -> None:
    """A chip belongs to one process, and this launcher hands no worker its
    own chips: several workers on one TPU host would all reach for the same
    ones and fail or hang. The host is told by the chips' device nodes — the
    launcher itself must stay off JAX."""
    import glob

    if nproc_per_node <= 1 or os.environ.get("JAX_PLATFORMS") == "cpu":
        return
    if glob.glob("/dev/accel*") or glob.glob("/dev/vfio/[0-9]*"):
        raise SystemExit(
            f"--nproc_per_node {nproc_per_node} on a TPU host: a chip "
            "belongs to one process and this launcher does not divide the "
            "host's chips between workers. Run one process per host (it "
            "drives every local chip), or set JAX_PLATFORMS=cpu for CPU "
            "workers.")


class Pod:
    """The local worker group: spawn, watch, restart (build_pod parity)."""

    def __init__(self, args, node_rank: int, nnodes: int, master: str):
        self.args = args
        self.node_rank = node_rank
        self.nnodes = nnodes
        self.master = master
        self.procs: list[subprocess.Popen] = []
        self.logs = []

    def reconfigure(self, node_rank: int, nnodes: int, master: str):
        """Re-env for a new membership epoch (elastic rank rebuild —
        reference: elastic/manager.py:126 _update_hosts + restart)."""
        self.node_rank = node_rank
        self.nnodes = nnodes
        self.master = master

    def worker_env(self, local_rank: int) -> dict:
        nproc = self.args.nproc_per_node
        world = self.nnodes * nproc
        rank = self.node_rank * nproc + local_rank
        env = dict(os.environ)
        env.update({
            "PADDLE_TRAINER_ID": str(rank),
            "PADDLE_LOCAL_RANK": str(local_rank),
            "PADDLE_TRAINERS_NUM": str(world),
            "PADDLE_NNODES": str(self.nnodes),
            "PADDLE_NODE_RANK": str(self.node_rank),
            "PADDLE_MASTER": self.master,
            "PADDLE_JOB_ID": self.args.job_id,
            # jax.distributed.initialize reads these in-process
            "JAX_COORDINATOR_ADDRESS": self.master,
            "JAX_NUM_PROCESSES": str(world),
            "JAX_PROCESS_ID": str(rank),
        })
        if self.args.devices:
            env["PADDLE_SELECTED_DEVICES"] = self.args.devices
        return env

    def start(self):
        os.makedirs(self.args.log_dir, exist_ok=True)
        self.stop()
        self.procs, self.logs = [], []
        for lr in range(self.args.nproc_per_node):
            rank = self.node_rank * self.args.nproc_per_node + lr
            log = open(os.path.join(self.args.log_dir,
                                    f"workerlog.{rank}"), "ab")
            cmd = [sys.executable, "-u", self.args.training_script,
                   *self.args.training_script_args]
            p = subprocess.Popen(cmd, env=self.worker_env(lr), stdout=log,
                                 stderr=subprocess.STDOUT)
            self.procs.append(p)
            self.logs.append(log)

    def poll(self):
        """Returns 'running' | 'done' | ('failed', rank)."""
        codes = [p.poll() for p in self.procs]
        if any(c not in (0, None) for c in codes):
            bad = next(i for i, c in enumerate(codes) if c not in (0, None))
            return ("failed", self.node_rank * self.args.nproc_per_node + bad)
        if all(c == 0 for c in codes):
            return "done"
        return "running"

    def stop(self, sig=signal.SIGTERM):
        for p in self.procs:
            if p.poll() is None:
                try:
                    p.send_signal(sig)
                except OSError:
                    pass
        deadline = time.time() + 10
        for p in self.procs:
            try:
                p.wait(timeout=max(0.1, deadline - time.time()))
            except subprocess.TimeoutExpired:
                p.kill()
        for log in self.logs:
            log.close()
        self.procs, self.logs = [], []


class ElasticController:
    """Membership watch + scale-up/down over the TCPStore.

    Reference: launch/controllers/master.py:186 (ETCDMaster's alive-node
    watch) + fleet/elastic/manager.py:126 (host-list update and restart).
    Each launcher heartbeats ``/elastic/hb/<uid>``; the master launcher
    (which hosts the store) computes the active set every tick and, when it
    changes within ``[min_nodes, max_nodes]``, publishes a new membership
    epoch. Every launcher follows epochs: stop pod, recompute node rank from
    the member list (master first, the rest in uid order), re-env, restart.
    The master launcher must stay alive — it IS the store (the reference has
    the same constraint on its etcd endpoint)."""

    HB_INTERVAL = 0.5
    HB_STALE = 3.0

    def __init__(self, store, uid: str, is_master: bool, min_nodes: int,
                 max_nodes: int, master_host: str, base_port: int):
        import threading

        self.store = store
        self.uid = uid
        self.is_master = is_master
        self.min_nodes = min_nodes
        self.max_nodes = max_nodes
        self.master_host = master_host
        self.base_port = base_port
        self.epoch = 0
        self.members: list[str] = []
        self._stop = threading.Event()
        self._hb = threading.Thread(target=self._heartbeat_loop, daemon=True)
        self._hb.start()

    def _heartbeat_loop(self):
        while not self._stop.wait(self.HB_INTERVAL):
            try:
                self.store.set(f"/elastic/hb/{self.uid}", repr(time.time()))
            except Exception:
                return

    def _roster(self) -> list[str]:
        """Every uid that ever announced (atomic slot-claim via add)."""
        n = int(self.store.add("/elastic/join_count", 0))
        out = []
        for i in range(1, n + 1):
            key = f"/elastic/join_name/{i}"
            if self.store.check(key):
                u = self.store.get(key).decode()
                if u not in out:
                    out.append(u)
        return out

    def _active_uids(self) -> list[str]:
        out = []
        now = time.time()
        for u in self._roster():
            try:
                ts = float(self.store.get(f"/elastic/hb/{u}").decode())
            except Exception:
                continue
            if now - ts < self.HB_STALE:
                out.append(u)
        return out

    def register(self):
        self.store.set(f"/elastic/hb/{self.uid}", repr(time.time()))
        slot = int(self.store.add("/elastic/join_count", 1))
        self.store.set(f"/elastic/join_name/{slot}", self.uid)

    def rejoin(self):
        """Leave under the old identity and re-register fresh (local worker
        failure: scale-down past us, then scale-up back in — reference
        elastic restart semantics)."""
        old = self.uid
        gen = int(old.rsplit("#", 1)[1]) + 1 if "#" in old else 1
        self.uid = f"{old.split('#', 1)[0]}#{gen}"
        try:
            self.store.set(f"/elastic/hb/{old}", repr(0.0))  # instantly stale
        except (OSError, RuntimeError):
            pass  # best-effort: peers age the heartbeat out on their own
        self.register()

    def manage(self):
        """Master tick: publish a new epoch when the active set changed and
        is within bounds."""
        if not self.is_master:
            return
        active = self._active_uids()
        # master first, others in stable uid order (keeps worker rank 0 — and
        # the workers' rendezvous host — on the store's node)
        ordered = ([self.uid] if self.uid in active else []) + sorted(
            u for u in active if u != self.uid)
        if len(ordered) < self.min_nodes:
            return  # wait for quorum (scale-up may re-add nodes)
        if len(ordered) > self.max_nodes:
            ordered = ordered[:self.max_nodes]
        if ordered != self.members or self.epoch == 0:
            self.epoch += 1
            self.members = ordered
            self.store.set(f"/elastic/members/{self.epoch}", ",".join(ordered))
            self.store.set("/elastic/epoch", str(self.epoch))

    def poll_epoch(self):
        """Returns (epoch, members) currently published (may be stale)."""
        if not self.store.check("/elastic/epoch"):
            return 0, []
        e = int(self.store.get("/elastic/epoch").decode())
        m = self.store.get(f"/elastic/members/{e}").decode().split(",")
        return e, m

    def worker_master_for(self, epoch: int) -> str:
        # fresh workers' rendezvous store per epoch (old ones may linger)
        return f"{self.master_host}:{self.base_port + 1 + epoch}"

    def stop(self):
        self._stop.set()


def _launch_auto_tuner(args) -> int:
    """Trial loop (reference: auto_tuner/tuner.py:21 driven from launch
    main.py): search -> prune (validity + memory model) -> run the training
    script once per surviving candidate -> record its metric -> emit
    ``best_cfg.json`` and ``history.csv``.

    Trial contract: each trial process receives the candidate as JSON in
    ``PADDLE_AUTO_TUNER_TRIAL`` and writes ``{"<metric>": value}`` to the
    path in ``PADDLE_AUTO_TUNER_RESULT`` (the reference greps trial logs for
    the metric; a result file is the explicit version of that contract).
    """
    import json

    from ..auto_tuner.tuner import AutoTuneConfig, Tuner

    with open(args.auto_tuner_json) as f:
        tj = json.load(f)
    cfg = AutoTuneConfig(
        num_devices=int(tj.get("num_devices", 8)),
        global_batch_size=int(tj.get("global_batch_size", 32)),
        model=tj.get("model", {}),
        memory_limit_gb=tj.get("memory_limit_gb"),
        max_trials=int(tj.get("max_trials", 0)),
        metric=tj.get("metric", "throughput"),
        higher_is_better=bool(tj.get("higher_is_better", True)),
    )
    tuner = Tuner(cfg)
    tdir = os.path.join(args.log_dir, "auto_tuner")
    os.makedirs(tdir, exist_ok=True)

    k = 0
    while True:
        cand = tuner.search_once()
        if cand is None:
            break
        res_path = os.path.join(tdir, f"trial_{k}.json")
        env = dict(os.environ)
        env["PADDLE_AUTO_TUNER_TRIAL"] = json.dumps(cand.as_dict())
        env["PADDLE_AUTO_TUNER_RESULT"] = res_path
        log_path = os.path.join(tdir, f"trial_{k}.log")
        with open(log_path, "ab") as log:
            proc = subprocess.Popen(
                [sys.executable, "-u", args.training_script,
                 *args.training_script_args],
                env=env, stdout=log, stderr=subprocess.STDOUT)
            rc = proc.wait()
        metric_value, err = None, None
        if rc == 0 and os.path.exists(res_path):
            with open(res_path) as f:
                metric_value = json.load(f).get(cfg.metric)
        elif rc != 0:
            err = f"trial exited rc={rc} (OOM or failure; see {log_path})"
        tuner.add_cfg(cand, metric_value, error=err)
        print(f"[auto-tuner] trial {k}: {cand.as_dict()} -> "
              f"{cfg.metric}={metric_value} err={err}", file=sys.stderr)
        k += 1

    tuner.recorder.store_history(os.path.join(tdir, "history.csv"))
    best = tuner.get_best_cfg()
    if best is not None:
        with open(os.path.join(tdir, "best_cfg.json"), "w") as f:
            json.dump(best, f, indent=1)
        print(json.dumps({"best_cfg": best}))
        return 0
    print(json.dumps({"best_cfg": None, "trials": k}))
    return 1


def launch(argv=None) -> int:
    """Run the launcher; returns the exit code (0 = all workers succeeded).

    Watcher loop parity: poll workers; on failure stop the pod and restart
    (all ranks restart together via the store's restart-epoch key) up to
    max_restart times. With ``--nnodes N:M`` the launcher becomes elastic:
    node leave/join within [N, M] re-ranks and restarts the job instead of
    failing it.
    """
    args = _parse_args(argv)
    _refuse_shared_chips(args.nproc_per_node)
    if args.auto_tuner_json:
        return _launch_auto_tuner(args)
    spec = str(args.nnodes)
    elastic = ":" in spec and args.master is not None
    nnodes = int(spec.split(":")[0])
    if elastic:
        min_nodes = int(spec.split(":")[0])
        max_nodes = int(spec.split(":")[1])
        return _launch_elastic(args, min_nodes, max_nodes)
    node_rank = args.rank if args.rank >= 0 else int(
        os.environ.get("PADDLE_NODE_RANK", 0))

    store = None
    worker_master = args.master
    if args.master is None:
        if nnodes > 1:
            raise ValueError("--master is required for multi-node jobs")
        # single node: reserve a free port for the WORKERS' rendezvous store
        # (worker rank 0 hosts it — the launcher must not bind it itself)
        import socket

        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            worker_master = f"127.0.0.1:{s.getsockname()[1]}"
    elif nnodes > 1:
        # launcher-level membership store lives on <port>; the trainers'
        # rendezvous store (hosted by worker rank 0) gets <port>+1
        from ..store import TCPStore

        host, _, port = args.master.rpartition(":")
        store = TCPStore(host, int(port), is_master=(node_rank == 0),
                         world_size=nnodes, timeout=args.elastic_timeout)
        store.set(f"/nodes/{node_rank}", str(os.getpid()))
        store.barrier("launch")
        worker_master = f"{host}:{int(port) + 1}"

    pod = Pod(args, node_rank, nnodes, worker_master)
    restarts = 0
    pod.start()
    try:
        while True:
            status = pod.poll()
            if status == "done":
                return 0
            if isinstance(status, tuple):  # failed
                _, bad_rank = status
                print(f"[launch] worker rank {bad_rank} failed "
                      f"(restart {restarts}/{args.max_restart})",
                      file=sys.stderr)
                pod.stop()
                if restarts >= args.max_restart:
                    return 1
                restarts += 1
                if store is not None and nnodes > 1:
                    # publish the restart epoch so every node restarts its pod
                    store.add("/restart_epoch", 1)
                pod.start()
            if store is not None and nnodes > 1:
                # follow restarts initiated by other nodes (check() is
                # non-blocking; get() would stall the watch loop)
                epoch = 0
                if store.check("/restart_epoch"):
                    epoch = int(store.get("/restart_epoch") or 0)
                if epoch > restarts:
                    pod.stop()
                    restarts = epoch
                    if restarts > args.max_restart:
                        return 1
                    pod.start()
            time.sleep(0.5)
    finally:
        pod.stop()
        if store is not None:
            store.close()


def _launch_elastic(args, min_nodes: int, max_nodes: int) -> int:
    """Elastic control loop: follow membership epochs, restart the pod with
    re-ranked env on every change; complete when the pod finishes."""
    from ..store import TCPStore

    host, _, port_s = args.master.rpartition(":")
    port = int(port_s)
    node_rank0 = args.rank if args.rank >= 0 else int(
        os.environ.get("PADDLE_NODE_RANK", 0))
    is_master = node_rank0 == 0
    uid = f"{node_rank0}-{os.getpid()}"
    store = TCPStore(host, port, is_master=is_master, world_size=1,
                     timeout=max(args.elastic_timeout, 10))
    ctrl = ElasticController(store, uid, is_master, min_nodes, max_nodes,
                             host, port)
    ctrl.register()
    pod = None
    cur_epoch = 0
    deadline = time.time() + args.elastic_timeout + 60
    def finish_ok() -> int:
        # publish our completion; the master lingers so peers can keep using
        # the store until their own pods drain
        try:
            store.set(f"/elastic/done/{ctrl.uid}", b"1")
        except (OSError, RuntimeError):
            pass  # best-effort: the master's linger window covers us
        if is_master:
            cap = time.time() + 30
            while time.time() < cap:
                try:
                    _, members = ctrl.poll_epoch()
                    if all(store.check(f"/elastic/done/{m}")
                           for m in members):
                        break
                except Exception:
                    break
                time.sleep(0.3)
        return 0

    try:
        while True:
            try:
                ctrl.manage()
                epoch, members = ctrl.poll_epoch()
            except Exception:
                # the master (store host) is gone: finish coordinator-less —
                # wait out the local pod and report its result
                if pod is not None:
                    for p in pod.procs:
                        p.wait()
                    status = pod.poll()
                    return 0 if status == "done" else 1
                return 1
            if epoch > cur_epoch:
                if ctrl.uid not in members:
                    print(f"[launch-elastic] epoch {epoch}: this node "
                          f"({ctrl.uid}) not in members {members}; exiting",
                          file=sys.stderr)
                    if pod is not None:
                        pod.stop()
                    # dropped from membership (scale-down past us): exit ok
                    if len(members) >= min_nodes:
                        return 0
                    return 1
                if pod is not None:
                    pod.stop()
                cur_epoch = epoch
                my_rank = members.index(ctrl.uid)
                wm = ctrl.worker_master_for(epoch)
                print(f"[launch-elastic] epoch {epoch}: {len(members)} "
                      f"nodes, this node rank {my_rank}", file=sys.stderr)
                pod = Pod(args, my_rank, len(members), wm)
                pod.start()
            if pod is not None:
                status = pod.poll()
                if status == "done":
                    return finish_ok()
                if isinstance(status, tuple):
                    # local worker failure: leave membership under the old
                    # identity and re-register fresh — peers see a leave+join
                    # and everyone restarts on the new epoch
                    _, bad = status
                    print(f"[launch-elastic] worker rank {bad} failed; "
                          "rejoining", file=sys.stderr)
                    pod.stop()
                    pod = None
                    ctrl.rejoin()
                    deadline = time.time() + args.elastic_timeout + 60
            elif time.time() > deadline:
                print("[launch-elastic] no quorum before timeout",
                      file=sys.stderr)
                return 1
            time.sleep(0.3)
    finally:
        ctrl.stop()
        if pod is not None:
            pod.stop()
        store.close(linger=0)


def main():
    sys.exit(launch())


if __name__ == "__main__":
    main()

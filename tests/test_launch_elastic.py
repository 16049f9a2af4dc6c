"""Elastic launch: membership scale-down and scale-up within --nnodes N:M.

Reference: launch/controllers/master.py:186 alive-node watch +
fleet/elastic/manager.py:126 host update/restart. Each "node" here is a real
launcher subprocess on localhost."""
import os
import signal
import socket
import subprocess
import sys
import tempfile
import time

import pytest

pytestmark = [pytest.mark.slow, pytest.mark.dist]  # elastic relaunch with real waits (~1.5 min)

SCRIPT = """
import os, sys, time
fail_dir = os.environ.get("FAIL_ONCE_DIR")
if fail_dir:
    marker = os.path.join(fail_dir, "failed_once")
    if not os.path.exists(marker):
        open(marker, "w").write("x")
        sys.exit(1)
rec = os.environ["REC_FILE"]
line = "%s/%s/%s" % (os.environ.get("PADDLE_NODE_RANK"),
                     os.environ.get("PADDLE_NNODES"),
                     os.environ.get("PADDLE_TRAINER_ID"))
with open(rec, "a") as f:
    f.write(line + "\\n")
time.sleep(float(os.environ.get("WORK_SECS", "8")))
"""


def _free_port():
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _start_launcher(tmp, port, rank, nnodes_spec, rec, work_secs="8",
                    extra_env=None):
    script = os.path.join(tmp, "worker.py")
    if not os.path.exists(script):
        open(script, "w").write(SCRIPT)
    env = dict(os.environ)
    env.update({"REC_FILE": rec, "WORK_SECS": work_secs,
                "JAX_PLATFORMS": "cpu"})
    env.update(extra_env or {})
    return subprocess.Popen(
        [sys.executable, "-m", "paddle_tpu.distributed.launch",
         "--nnodes", nnodes_spec, "--master", f"127.0.0.1:{port}",
         "--rank", str(rank), "--log_dir", os.path.join(tmp, f"log{rank}"),
         "--elastic_timeout", "20", script],
        env=env, cwd="/root/repo", start_new_session=True,
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True)


def _lines(rec):
    if not os.path.exists(rec):
        return []
    return [l for l in open(rec).read().splitlines() if l]


def _wait_lines(rec, n, timeout):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if len(_lines(rec)) >= n:
            return True
        time.sleep(0.3)
    return False


class TestElasticLaunch:
    def test_scale_down_completes_with_fewer_nodes(self, tmp_path):
        """Kill one node of 3 (min 2): survivors re-rank to world 2 and the
        job completes."""
        tmp = str(tmp_path)
        rec = os.path.join(tmp, "rec.txt")
        port = _free_port()
        procs = [_start_launcher(tmp, port, r, "2:3", rec) for r in range(3)]
        try:
            assert _wait_lines(rec, 3, 40), f"epoch-1 never formed: {_lines(rec)}"
            # SIGKILL node 2's whole process group (launcher + its worker)
            os.killpg(os.getpgid(procs[2].pid), signal.SIGKILL)
            rcs = [procs[0].wait(timeout=90), procs[1].wait(timeout=90)]
            assert rcs == [0, 0], (procs[0].stdout.read(),
                                   procs[1].stdout.read())
            lines = _lines(rec)
            # second epoch ran with 2 nodes
            assert any(l.split("/")[1] == "2" for l in lines), lines
        finally:
            for p in procs:
                try:
                    os.killpg(os.getpgid(p.pid), signal.SIGKILL)
                except (ProcessLookupError, PermissionError):
                    pass

    def test_scale_up_adds_node(self, tmp_path):
        """Start 2 nodes (min 2, max 3); a third joins mid-run and the job
        re-forms with world 3."""
        tmp = str(tmp_path)
        rec = os.path.join(tmp, "rec.txt")
        port = _free_port()
        procs = [_start_launcher(tmp, port, r, "2:3", rec, work_secs="10")
                 for r in range(2)]
        try:
            assert _wait_lines(rec, 2, 40), f"epoch-1 never formed: {_lines(rec)}"
            procs.append(_start_launcher(tmp, port, 2, "2:3", rec,
                                         work_secs="10"))
            rcs = [p.wait(timeout=120) for p in procs]
            assert all(rc == 0 for rc in rcs), [p.stdout.read() for p in procs]
            lines = _lines(rec)
            assert any(l.split("/")[1] == "3" for l in lines), lines
        finally:
            for p in procs:
                try:
                    os.killpg(os.getpgid(p.pid), signal.SIGKILL)
                except (ProcessLookupError, PermissionError):
                    pass


    def test_local_worker_failure_rejoins(self, tmp_path):
        """A crashing worker makes its node leave+rejoin; every node
        restarts on the new epoch and the job completes."""
        tmp = str(tmp_path)
        rec = os.path.join(tmp, "rec.txt")
        port = _free_port()
        fail_dir = os.path.join(tmp, "failmark")
        os.makedirs(fail_dir)
        procs = [
            _start_launcher(tmp, port, 0, "2:2", rec, work_secs="6"),
            _start_launcher(tmp, port, 1, "2:2", rec, work_secs="6",
                            extra_env={"FAIL_ONCE_DIR": fail_dir}),
        ]
        try:
            rcs = [p.wait(timeout=120) for p in procs]
            assert all(rc == 0 for rc in rcs), [p.stdout.read() for p in procs]
            lines = _lines(rec)
            # epoch 1 (failed node silent) + epoch 2 with both nodes again
            assert sum(1 for l in lines if l.split("/")[1] == "2") >= 3, lines
            assert os.path.exists(os.path.join(fail_dir, "failed_once"))
        finally:
            for p in procs:
                try:
                    os.killpg(os.getpgid(p.pid), signal.SIGKILL)
                except (ProcessLookupError, PermissionError):
                    pass

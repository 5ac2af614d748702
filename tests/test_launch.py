"""Launch CLI: env contract + 2-process CPU rendezvous
(reference: python/paddle/distributed/launch/main.py:18, test pattern:
test_collective_base.py subprocess launch)."""
import os
import subprocess
import sys

import pytest

pytestmark = pytest.mark.slow  # fast lane: -m 'not slow'

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

WORKER = """
import json, os, sys
sys.path.insert(0, {repo!r})
from paddle_tpu.distributed.env import ParallelEnv, get_rank, get_world_size

env = ParallelEnv()
info = dict(rank=get_rank(), world=get_world_size(),
            local_rank=env.local_rank,
            endpoint=env.current_endpoint,
            n_endpoints=len(env.trainer_endpoints),
            master=os.environ["MASTER_ADDR"] + ":" + os.environ["MASTER_PORT"])
with open(os.path.join({out!r}, f"rank{{info['rank']}}.json"), "w") as f:
    json.dump(info, f)
"""

RENDEZVOUS_WORKER = """
import os, sys
sys.path.insert(0, {repo!r})
os.environ["JAX_PLATFORMS"] = "cpu"
import jax
jax.config.update("jax_platforms", "cpu")
import paddle_tpu.distributed as dist
dist.init_parallel_env()
assert jax.distributed.is_initialized()
r = jax.process_index()
n = jax.process_count()
assert n == 2, n
with open(os.path.join({out!r}, f"rdv{{r}}.ok"), "w") as f:
    f.write(str(n))
"""


def _run_launch(script_path, tmp_path, nproc=2, extra=()):
    cmd = [sys.executable, "-m", "paddle_tpu.distributed.launch",
           "--nproc_per_node", str(nproc),
           "--log_dir", str(tmp_path / "log"), *extra, str(script_path)]
    env = dict(os.environ, PYTHONPATH=REPO + os.pathsep
               + os.environ.get("PYTHONPATH", ""))
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run(cmd, cwd=REPO, env=env, capture_output=True,
                          text=True, timeout=300)


def test_env_contract_two_workers(tmp_path):
    script = tmp_path / "worker.py"
    script.write_text(WORKER.format(repo=REPO, out=str(tmp_path)))
    r = _run_launch(script, tmp_path)
    assert r.returncode == 0, r.stderr[-2000:]
    import json

    infos = []
    for rank in (0, 1):
        p = tmp_path / f"rank{rank}.json"
        assert p.exists(), f"worker {rank} wrote no output; {r.stderr[-500:]}"
        infos.append(json.loads(p.read_text()))
    assert {i["rank"] for i in infos} == {0, 1}
    assert all(i["world"] == 2 for i in infos)
    assert all(i["n_endpoints"] == 2 for i in infos)
    assert infos[0]["endpoint"] != infos[1]["endpoint"]
    assert infos[0]["master"] == infos[1]["master"]
    assert {i["local_rank"] for i in infos} == {0, 1}


def test_rendezvous_jax_distributed(tmp_path):
    """Both workers initialize the JAX coordination service from the launch
    env (MASTER_ADDR/PORT) — a real cross-process rendezvous."""
    script = tmp_path / "rdv.py"
    script.write_text(RENDEZVOUS_WORKER.format(repo=REPO, out=str(tmp_path)))
    r = _run_launch(script, tmp_path)
    assert r.returncode == 0, r.stderr[-2000:]
    assert (tmp_path / "rdv0.ok").exists()
    assert (tmp_path / "rdv1.ok").exists()


def test_failed_worker_terminates_job(tmp_path):
    script = tmp_path / "boom.py"
    script.write_text(
        "import os, sys, time\n"
        "if os.environ['PADDLE_TRAINER_ID'] == '1':\n"
        "    sys.exit(3)\n"
        "time.sleep(60)\n")
    r = _run_launch(script, tmp_path)
    assert r.returncode == 3


PS_SCRIPT = """
import json, os, sys, time
sys.path.insert(0, {repo!r})
role = os.environ["TRAINING_ROLE"]
info = dict(role=role,
            rank=int(os.environ["PADDLE_TRAINER_ID"]),
            servers=os.environ["PADDLE_PSERVERS_IP_PORT_LIST"].split(","),
            n_trainers=int(os.environ["PADDLE_TRAINERS_NUM"]),
            port=os.environ["PADDLE_PORT"])
with open(os.path.join({out!r}, f"{{role}}{{info['rank']}}.json"), "w") as f:
    json.dump(info, f)
if role == "PSERVER":
    time.sleep(600)   # servers run until the launcher stops them
"""


def test_ps_mode_servers_and_trainers(tmp_path):
    """PS controller (reference: launch/controllers/ps.py): one script,
    role from TRAINING_ROLE; servers terminated after trainers finish."""
    import json
    import time

    script = tmp_path / "ps.py"
    script.write_text(PS_SCRIPT.format(repo=REPO, out=str(tmp_path)))
    t0 = time.time()
    r = _run_launch(script, tmp_path,
                    extra=("--run_mode", "ps", "--server_num", "2",
                           "--trainer_num", "2"))
    assert r.returncode == 0, r.stderr[-2000:]
    assert time.time() - t0 < 120  # servers did not outlive the trainers
    roles = {}
    for f in tmp_path.glob("*.json"):
        info = json.loads(f.read_text())
        roles.setdefault(info["role"], []).append(info)
    assert len(roles.get("PSERVER", [])) == 2
    assert len(roles.get("TRAINER", [])) == 2
    assert all(len(i["servers"]) == 2 for i in roles["TRAINER"])
    assert all(i["n_trainers"] == 2 for i in roles["TRAINER"])


RPC_SCRIPT = """
import json, os, sys

sys.path.insert(0, {repo!r})
info = dict(rank=int(os.environ["PADDLE_TRAINER_ID"]),
            world=int(os.environ["PADDLE_TRAINERS_NUM"]),
            endpoint=os.environ["PADDLE_WORKER_ENDPOINT"],
            master=os.environ["PADDLE_MASTER_ENDPOINT"])
with open(os.path.join({out!r}, f"rpc{{info['rank']}}.json"), "w") as f:
    json.dump(info, f)
"""


def test_rpc_mode_env_contract(tmp_path):
    """RPC controller (reference: launch/controllers/rpc.py): the env
    contract init_rpc consumes (distributed/rpc/rpc.py:174)."""
    import json

    script = tmp_path / "rpc.py"
    script.write_text(RPC_SCRIPT.format(repo=REPO, out=str(tmp_path)))
    r = _run_launch(script, tmp_path, extra=("--run_mode", "rpc"))
    assert r.returncode == 0, r.stderr[-2000:]
    infos = [json.loads((tmp_path / f"rpc{i}.json").read_text())
             for i in range(2)]
    assert [i["rank"] for i in infos] == [0, 1]
    assert all(i["world"] == 2 for i in infos)
    assert infos[0]["master"] == infos[1]["master"]
    assert infos[0]["endpoint"] != infos[1]["endpoint"]


def test_unknown_run_mode_rejected(tmp_path):
    script = tmp_path / "x.py"
    script.write_text("pass\n")
    r = _run_launch(script, tmp_path, extra=("--run_mode", "bogus"))
    assert r.returncode != 0
    assert "collective" in r.stderr

"""TCPStore (native C++ core) + paddle_tpu.distributed.rpc.

Mirrors the reference's rpc test strategy (test_rpc_*.py under
python/paddle/fluid/tests): single-worker loopback RPC, then a real
2-process job rendezvousing through the store.
"""
import os
import socket
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _free_port():
    with socket.socket() as s:
        s.bind(("", 0))
        return s.getsockname()[1]


# ---------------------------------------------------------------- TCPStore


def test_tcp_store_set_get_add_wait_check():
    from paddle_tpu.distributed import TCPStore

    port = _free_port()
    master = TCPStore("127.0.0.1", port, is_master=True, world_size=2,
                      timeout=20)
    client = TCPStore("127.0.0.1", port, is_master=False, world_size=2,
                      timeout=20)
    try:
        master.set("alpha", b"hello")
        assert client.get("alpha") == b"hello"
        assert client.add("ctr", 3) == 3
        assert master.add("ctr", 4) == 7
        assert client.get("ctr") == b"7"
        assert not client.check("missing")
        with pytest.raises(TimeoutError):
            client.wait("missing", timeout=0.3)
        client.set("beta", "text-value")
        master.wait(["alpha", "beta"], timeout=5)
        assert master.check(["alpha", "beta"])
        assert master.get("beta") == b"text-value"
    finally:
        client.stop()
        master.stop()


def test_tcp_store_blocking_get_crosses_threads():
    import threading

    from paddle_tpu.distributed import TCPStore

    port = _free_port()
    store = TCPStore("127.0.0.1", port, is_master=True, timeout=20)
    try:
        def late_set():
            TCPStore("127.0.0.1", port, timeout=10).set("late", b"v")

        t = threading.Timer(0.3, late_set)
        t.start()
        assert store.get("late", timeout=10) == b"v"  # blocks until set
        t.join()
    finally:
        store.stop()


# ---------------------------------------------------------------- rpc


def _square(x):
    return x * x


def _raise_value_error():
    raise ValueError("remote boom")


def test_rpc_single_worker_loopback():
    from paddle_tpu.distributed import rpc

    rpc.init_rpc("worker0", rank=0, world_size=1,
                 master_endpoint=f"127.0.0.1:{_free_port()}")
    try:
        assert rpc.rpc_sync("worker0", _square, args=(7,)) == 49
        fut = rpc.rpc_async("worker0", _square, args=(9,))
        assert fut.wait() == 81
        info = rpc.get_worker_info("worker0")
        assert info.rank == 0 and info.name == "worker0"
        assert rpc.get_current_worker_info().name == "worker0"
        assert [w.name for w in rpc.get_all_worker_infos()] == ["worker0"]
        with pytest.raises(ValueError, match="remote boom"):
            rpc.rpc_sync("worker0", _raise_value_error)
        with pytest.raises(ValueError, match="unknown rpc worker"):
            rpc.rpc_sync("nobody", _square, args=(1,))
    finally:
        rpc.shutdown()


_TWO_PROC_SCRIPT = textwrap.dedent("""
    import os, sys
    sys.path.insert(0, {repo!r})
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    from paddle_tpu.distributed import rpc

    rank = int(sys.argv[1])
    port = sys.argv[2]

    def mul(a, b):
        return a * b

    rpc.init_rpc(f"worker{{rank}}", rank=rank, world_size=2,
                 master_endpoint=f"127.0.0.1:{{port}}")
    other = f"worker{{1 - rank}}"
    # both directions at once: each worker calls the *other* one
    assert rpc.rpc_sync(other, mul, args=(rank + 2, 10)) == (rank + 2) * 10
    infos = rpc.get_all_worker_infos()
    assert [w.name for w in infos] == ["worker0", "worker1"]
    rpc.shutdown()
    print(f"RANK{{rank}}_OK")
""")


def test_rpc_two_process_job(tmp_path):
    port = _free_port()
    script = tmp_path / "rpc_worker.py"
    script.write_text(_TWO_PROC_SCRIPT.format(repo=REPO))
    env = dict(os.environ, JAX_PLATFORMS="cpu")  # workers never need a chip
    procs = [subprocess.Popen([sys.executable, str(script), str(r), str(port)],
                              stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                              text=True, env=env)
             for r in range(2)]
    outs = []
    for p in procs:
        out, err = p.communicate(timeout=120)
        outs.append((p.returncode, out, err))
    for r, (rc, out, err) in enumerate(outs):
        assert rc == 0, f"rank {r} failed:\n{out}\n{err}"
        assert f"RANK{r}_OK" in out

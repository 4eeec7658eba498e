"""Multi-host DCN smoke test (SURVEY.md §5 "Distributed communication
backend"): two OS processes bring up jax.distributed over a local
coordinator, build a global mesh with znicz_tpu.parallel.mesh, and psum
across process boundaries — the collective result proves DCN wiring."""

import os
import socket
import subprocess
import sys
import textwrap

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

WORKER = textwrap.dedent("""\
    import sys

    from znicz_tpu.virtdev import provision_cpu_devices

    # verify=False: the count check would initialize the backend, which
    # must not happen before jax.distributed.initialize
    provision_cpu_devices(1, verify=False)
    from znicz_tpu.parallel.mesh import distributed_init, make_mesh

    pid, n, port = int(sys.argv[1]), int(sys.argv[2]), sys.argv[3]
    distributed_init(coordinator=f"127.0.0.1:{port}",
                     num_processes=n, process_id=pid)
    import numpy as np

    import jax
    from jax.sharding import PartitionSpec as P

    assert jax.process_count() == n, jax.process_count()
    d = len(jax.devices())                   # global across BOTH processes
    assert d > len(jax.local_devices()), "no cross-process devices visible"
    mesh = make_mesh(axes=("data",))         # all d global devices
    psum = jax.shard_map(lambda x: jax.lax.psum(x, "data"), mesh=mesh,
                         in_specs=P("data"), out_specs=P())
    # every process passes the same [0..d) array; jit shards it over the
    # global mesh, so the psum crosses the process (DCN) boundary
    x = np.arange(float(d), dtype=np.float32)
    total = float(np.asarray(jax.jit(psum)(x))[0])
    assert total == sum(range(d)), (total, d)
    print(f"proc {pid} dcn_ok devices={d} procs={n}", flush=True)
""")


def test_two_process_dcn_psum(tmp_path):
    worker = tmp_path / "dcn_worker.py"
    worker.write_text(WORKER)
    with socket.socket() as s:                # free coordinator port
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    n = 2
    env = dict(os.environ)                    # script dir != repo: put the
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    procs = [subprocess.Popen(
        [sys.executable, str(worker), str(pid), str(n), str(port)],
        cwd=REPO, env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
        text=True) for pid in range(n)]
    outs = []
    try:
        for proc in procs:
            stdout, stderr = proc.communicate(timeout=180)
            assert proc.returncode == 0, stderr[-2000:]
            outs.append(stdout)
    finally:
        for proc in procs:
            if proc.poll() is None:
                proc.kill()
                proc.communicate()
    for pid, out in enumerate(outs):
        assert f"proc {pid} dcn_ok" in out and f"procs={n}" in out, out

"""Multi-host runtime helper (parallel/distributed.py): single-host no-op
behavior in-process, and a real 1-process coordinator bring-up in a
subprocess (jax.distributed with num_processes=1 runs the full coordinator
handshake without needing a second machine)."""

import socket
import subprocess
import sys

import numpy as np
import pytest

from pio_tpu.parallel.distributed import (
    distributed_env,
    initialize_distributed,
    is_primary,
    runtime_info,
)

# the 2-process tests dispatch real cross-process collectives on the CPU
# backend over gloo (jax's default jax_cpu_collectives_implementation)


def test_single_host_is_noop(monkeypatch):
    monkeypatch.delenv("PIO_TPU_COORDINATOR", raising=False)
    assert distributed_env() is None
    assert initialize_distributed() is False
    assert is_primary()
    info = runtime_info()
    assert info["process_count"] == 1
    assert info["global_devices"] >= 1
    assert info["distributed"] is False


def test_env_parsing(monkeypatch):
    monkeypatch.setenv("PIO_TPU_COORDINATOR", "10.0.0.1:8476")
    monkeypatch.setenv("PIO_TPU_NUM_PROCESSES", "4")
    monkeypatch.setenv("PIO_TPU_PROCESS_ID", "2")
    assert distributed_env() == {
        "coordinator_address": "10.0.0.1:8476",
        "num_processes": 4,
        "process_id": 2,
    }


def test_partial_env_fails_fast(monkeypatch):
    # Validation happens on the MERGED args+env config: coordinator from env
    # with no counts anywhere fails fast...
    monkeypatch.setenv("PIO_TPU_COORDINATOR", "10.0.0.1:8476")
    monkeypatch.delenv("PIO_TPU_NUM_PROCESSES", raising=False)
    monkeypatch.delenv("PIO_TPU_PROCESS_ID", raising=False)
    with pytest.raises(ValueError, match="num_processes"):
        initialize_distributed()


def test_mixed_env_and_args_is_complete(monkeypatch):
    # ...but coordinator from env + counts passed as arguments is a complete
    # config: it must get past validation (the launcher pattern flagged in
    # round-1 advice). jax.distributed.initialize would block dialing the
    # fake coordinator, so assert via distributed_env alone.
    monkeypatch.setenv("PIO_TPU_COORDINATOR", "10.0.0.1:8476")
    monkeypatch.delenv("PIO_TPU_NUM_PROCESSES", raising=False)
    monkeypatch.delenv("PIO_TPU_PROCESS_ID", raising=False)
    assert distributed_env() == {"coordinator_address": "10.0.0.1:8476"}


_CHILD = """
import os, sys
port, pid, expected_path = sys.argv[1], int(sys.argv[2]), sys.argv[3]
storage_port = int(sys.argv[4]) if len(sys.argv) > 4 else None
os.environ["PIO_TPU_COORDINATOR"] = "127.0.0.1:" + port
os.environ["PIO_TPU_NUM_PROCESSES"] = "2"
os.environ["PIO_TPU_PROCESS_ID"] = str(pid)
import jax
sys.path.insert(0, "{repo}")
sys.path.insert(0, "{repo}/tests")
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 2)
from pio_tpu.parallel.distributed import initialize_distributed, runtime_info
assert initialize_distributed() is True
info = runtime_info()
assert info["process_count"] == 2, info
assert info["global_devices"] == 4, info

import numpy as np
from pio_tpu.parallel.mesh import MeshConfig, create_mesh
from _dist_workload import run_workload

mesh = create_mesh(MeshConfig(data=2, seq=1, model=2))
uf, itf, losses = run_workload(mesh, storage_port=storage_port)
exp = np.load(expected_path)
np.testing.assert_allclose(uf, exp["uf"], atol=2e-4)
np.testing.assert_allclose(itf, exp["itf"], atol=2e-4)
np.testing.assert_allclose(losses, exp["losses"], atol=2e-4)
print("CHILD_OK", pid, flush=True)
"""


def _coordinator_port() -> int:
    """A bind-tested free port BELOW the kernel's ephemeral range
    (/proc/sys/net/ipv4/ip_local_port_range). The coordinator port is
    handed to the children as a bare number — nothing holds it between
    our probe and the child's bind — so a pick from the ephemeral range
    can be grabbed meanwhile by any unrelated outbound socket under
    full-suite load, cross-connecting gloo's TCP pairs (the
    'op.preamble.length <= op.nbytes' flake). Ports below the floor are
    never auto-assigned to outbound connections, which removes that
    race instead of retrying around it."""
    import random

    try:
        with open("/proc/sys/net/ipv4/ip_local_port_range") as f:
            floor = int(f.read().split()[0])
    except (OSError, ValueError, IndexError):
        floor = 32768                       # the kernel default
    lo, hi = max(10240, floor - 22000), floor
    for _ in range(64):
        port = random.randrange(lo, hi)
        with socket.socket() as s:
            try:
                s.bind(("127.0.0.1", port))
            except OSError:
                continue                    # a listener lives there
            return port
    # sub-range exhausted (unheard of on loopback): ephemeral fallback
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _run_two_children(code, expected, extra=()):
    """Spawn the 2-process distributed child pair on a freshly chosen
    coordinator port; -> [(stdout, stderr)] per child."""
    port = _coordinator_port()
    procs = [
        subprocess.Popen(
            [sys.executable, "-c", code, str(port), str(pid), str(expected),
             *extra],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
            cwd="/root/repo",
        )
        for pid in range(2)
    ]
    outs = []
    for proc in procs:
        try:
            out, err = proc.communicate(timeout=420)
        except subprocess.TimeoutExpired:
            for p in procs:
                p.kill()
            raise
        outs.append((out, err))
    return outs


def _assert_children_ok(code, expected, extra=()):
    """Run the child pair; on gloo's TCP-pair handshake failure
    (gloo::EnforceNotMet, 'op.preamble.length <= op.nbytes') retry ONCE
    on a FRESH coordinator port — _run_two_children picks a new one per
    call, so the retry never re-rolls the dice on the same port the way
    the old bounded same-port retry did. With coordinator ports now
    outside the ephemeral range the race itself is gone; the fresh-port
    retry is the backstop for a stray listener appearing between the
    bind-probe and the children's bring-up. Only that signature
    retries; any other failure, or a second gloo failure, still fails
    the test."""
    for attempt in (0, 1):
        outs = _run_two_children(code, expected, extra)
        if all(f"CHILD_OK {pid}" in out
               for pid, (out, _err) in enumerate(outs)):
            return
        gloo_race = any("gloo::EnforceNotMet" in err for _out, err in outs)
        if not gloo_race or attempt:
            break
    for pid, (out, err) in enumerate(outs):
        assert f"CHILD_OK {pid}" in out, f"process {pid} failed:\n{err}"


def test_two_process_collectives_match_single_process(tmp_path):
    """Two real OS processes join one distributed runtime (2 procs x 2 local
    CPU devices = 4 global) and run sharded ALS + dp x tp two-tower steps
    whose collectives cross the process boundary; both must reproduce the
    single-process 4-device results. The reference's cross-executor story is
    Spark's shuffle machinery (tested upstream); here the cross-process data
    plane is ours, so it gets a real 2-process test."""
    from pio_tpu.parallel.mesh import MeshConfig, create_mesh
    from _dist_workload import run_workload

    # single-process reference on an identically-shaped 4-device mesh
    import jax

    ref_mesh = create_mesh(
        MeshConfig(data=2, seq=1, model=2), devices=jax.devices()[:4]
    )
    uf, itf, losses = run_workload(ref_mesh)
    expected = tmp_path / "expected.npz"
    np.savez(expected, uf=uf, itf=itf, losses=losses)

    code = _CHILD.format(repo="/root/repo")
    _assert_children_ok(code, expected)


def test_two_process_training_from_shared_storage_server(tmp_path):
    """The full multi-host data plane, ours end to end: a storage server
    owns the events; TWO OS processes join one jax.distributed runtime,
    each mounts the server over HTTP, reads the same columnarized COO
    (EventStore.interactions), and trains sharded ALS + dp x tp
    two-tower with cross-process collectives — results must match a
    single-process 4-device run reading from the SAME server. The
    reference leans on Spark+HBase for exactly this (SURVEY §4: no
    multi-node tests upstream); here it is tested for real."""
    from pio_tpu.data.storage import Storage
    from pio_tpu.parallel.mesh import MeshConfig, create_mesh
    from pio_tpu.server.storageserver import (
        StorageServerConfig, create_storage_server,
    )
    from _dist_workload import run_workload, seed_shared_storage

    backing = Storage(env={
        "PIO_STORAGE_SOURCES_M_TYPE": "memory",
        "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "M",
        "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "M",
        "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "M",
    })
    seed_shared_storage(backing)
    server = create_storage_server(
        backing, StorageServerConfig(ip="127.0.0.1", port=0))
    server.start()
    try:
        import jax

        ref_mesh = create_mesh(
            MeshConfig(data=2, seq=1, model=2), devices=jax.devices()[:4]
        )
        uf, itf, losses = run_workload(ref_mesh, storage_port=server.port)
        expected = tmp_path / "expected_shared.npz"
        np.savez(expected, uf=uf, itf=itf, losses=losses)

        code = _CHILD.format(repo="/root/repo")
        _assert_children_ok(code, expected, extra=(str(server.port),))
    finally:
        server.stop()
        backing.close()


def test_real_coordinator_single_process():
    """End-to-end: a subprocess joins a real (1-process) distributed runtime
    via the env vars, builds a workflow context, and runs a psum."""
    port = _coordinator_port()
    code = f"""
import os
os.environ["PIO_TPU_COORDINATOR"] = "127.0.0.1:{port}"
os.environ["PIO_TPU_NUM_PROCESSES"] = "1"
os.environ["PIO_TPU_PROCESS_ID"] = "0"
import jax
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 4)
from pio_tpu.parallel.distributed import initialize_distributed, runtime_info
assert initialize_distributed() is True
info = runtime_info()
assert info["distributed"] and info["process_count"] == 1
assert info["global_devices"] == 4

from pio_tpu.data.storage import Storage
from pio_tpu.workflow.context import create_workflow_context
ctx = create_workflow_context(
    Storage(env={{"PIO_STORAGE_SOURCES_M_TYPE": "memory",
                  "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "M",
                  "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "M",
                  "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "M"}})
)
assert ctx.mesh is not None and ctx.mesh.devices.size == 4

import jax.numpy as jnp
from functools import partial
from jax.sharding import PartitionSpec as P
out = jax.shard_map(
    lambda x: jax.lax.psum(x, "data"), mesh=ctx.mesh,
    in_specs=P("data"), out_specs=P(), check_vma=False,
)(jnp.ones(4))
assert float(out[0]) == 4.0
print("DISTRIBUTED_OK")
"""
    proc = subprocess.run(
        [sys.executable, "-c", code],
        capture_output=True, text=True, timeout=300,
        cwd="/root/repo",
    )
    assert "DISTRIBUTED_OK" in proc.stdout, proc.stderr

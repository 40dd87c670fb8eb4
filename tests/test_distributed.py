"""Multi-process (DCN) smoke test: two OS processes join one JAX world
through parallel.distributed.initialize and run a psum whose operands
live in different processes.

This is the boundary the 8-device virtual mesh cannot reach: that mesh
is one process, so its collectives never cross a process gap. Here the
coordinator handshake, the global device view (2 processes x 1 CPU
device), make_array_from_process_local_data, and a cross-process psum
all run for real — the same code path a TPU pod uses over DCN
(SURVEY.md §2.8), shrunk to two local CPU processes.
"""
from __future__ import annotations

import os
import socket
import subprocess
import sys

import pytest

_WORKER = r"""
import jax
import jax.numpy as jnp
import numpy as np
from functools import partial
from jax.sharding import NamedSharding, PartitionSpec as P

from foremast_tpu.parallel import distributed as D
from jax import shard_map
from foremast_tpu.parallel.mesh import FLEET_AXIS

did_init = D.initialize()  # env contract: COORDINATOR_ADDRESS/NUM_PROCESSES/PROCESS_ID
assert did_init, "initialize() must join the 2-process world"
assert jax.process_count() == 2, jax.process_count()

info = D.host_info()
assert info.num_processes == 2
assert info.global_devices == 2, info.global_devices

mesh = D.global_fleet_mesh()
global_batch = 4
sl = D.process_batch_slice(global_batch, info)
full = np.arange(1.0, global_batch + 1.0, dtype=np.float32)  # 1+2+3+4 = 10
arr = jax.make_array_from_process_local_data(
    NamedSharding(mesh, P(FLEET_AXIS)), full[sl], (global_batch,)
)

@partial(shard_map, mesh=mesh, in_specs=P(FLEET_AXIS), out_specs=P())
def total(x):
    return jax.lax.psum(jnp.sum(x), FLEET_AXIS)

out = jax.jit(total)(arr)
print("PSUM_TOTAL", float(out), flush=True)
assert float(out) == 10.0, float(out)
"""


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _run_two_workers(worker_src: str, timeout: float, what: str) -> str:
    """Launch two single-device CPU processes joined via a local
    coordinator; return combined output (any worker failure fails)."""
    port = _free_port()
    procs = []
    for rank in range(2):
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
        env["COORDINATOR_ADDRESS"] = f"127.0.0.1:{port}"
        env["NUM_PROCESSES"] = "2"
        env["PROCESS_ID"] = str(rank)
        procs.append(
            subprocess.Popen(
                [sys.executable, "-c", worker_src],
                env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
                text=True, cwd=os.path.dirname(os.path.dirname(__file__)),
            )
        )
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=timeout)
            outs.append(out)
    except subprocess.TimeoutExpired:
        for p in procs:
            p.kill()
        pytest.fail(f"{what} workers timed out")
    combined = "\n\n".join(outs)
    if any(p.returncode != 0 for p in procs):
        pytest.fail(f"{what} failed:\n{combined[-4000:]}")
    return combined


@pytest.mark.slow
def test_two_process_psum_over_coordinator():
    combined = _run_two_workers(_WORKER, 180, "DCN smoke")
    # both ranks computed the same global reduction over DCN
    assert combined.count("PSUM_TOTAL 10.0") == 2, combined[-2000:]

_SCORER_WORKER = r"""
import numpy as np
import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from foremast_tpu.parallel import distributed as D
from foremast_tpu.parallel import fleet as fl
from foremast_tpu.parallel.mesh import FLEET_AXIS

assert D.initialize(), "initialize() must join the 2-process world"
info = D.host_info()
mesh = D.global_fleet_mesh()

B, T = 4, 32
rng = np.random.default_rng(0)
base = rng.normal(10.0, 1.0, (B, T)).astype(np.float32)
cur = base.copy()
cur[1] += 100.0  # row 1 is catastrophically shifted
cur[3] += 100.0  # row 3 too
mask = np.ones((B, T), bool)

def g(a):
    # identical full array on every process; each contributes its slice
    sl = D.process_batch_slice(a.shape[0], info)
    return jax.make_array_from_process_local_data(
        NamedSharding(mesh, P(FLEET_AXIS)), a[sl], a.shape
    )

cfg = {
    "pvalue_threshold": np.full(B, 0.01, np.float32),
    "test_mask": np.full(B, 0b1111, np.int32),
    "combine": np.zeros(B, np.int32),
    "ma_window": np.full(B, 10, np.int32),
    "band_threshold": np.full(B, 3.0, np.float32),
    "bound_mode": np.zeros(B, np.int32),
    "min_lower_bound": np.zeros(B, np.float32),
}
run = fl.make_fleet_scorer(mesh, k=2)
args = [g(a) for a in (base, mask, cur, mask)]
gcfg = {k: g(v) for k, v in cfg.items()}
out, total, top_v, top_idx = run(*args, gcfg)
from jax.experimental import multihost_utils as mh
flags = np.asarray(mh.process_allgather(out["unhealthy"], tiled=True))
print("FLEET_FLAGS", "".join("U" if f else "h" for f in flags),
      "TOTAL", total, "TOPIDX", sorted(int(i) for i in np.asarray(top_idx)[:2]),
      flush=True)
assert total == 2, total
assert list(flags) == [False, True, False, True], flags
"""


@pytest.mark.slow
def test_two_process_fleet_scorer_over_coordinator():
    """The ACTUAL sharded fleet program (make_fleet_scorer: vmapped verdicts
    + psum unhealthy-count + all-gathered top-k) across two OS processes —
    the full multi-pod scoring path, shrunk to 2 CPU procs over DCN."""
    combined = _run_two_workers(_SCORER_WORKER, 240, "fleet-scorer DCN")
    # both ranks agree: rows 1 and 3 unhealthy, fleet total 2, top-k global
    assert combined.count("FLEET_FLAGS hUhU TOTAL 2 TOPIDX [1, 3]") == 2, \
        combined[-2000:]

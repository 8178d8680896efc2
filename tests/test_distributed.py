"""Distributed runtime: shard_map MapReduce on 8 placeholder devices,
ring all-gather vs reference, fault/straggler policies, sharding rules."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.distributed.fault import (FaultEvent, FaultPlan, RestartPolicy,
                                     detect_stragglers)
from repro.core.hetero import HeterogeneityProfile

SCRIPT = r'''
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import numpy as np
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.core.mapreduce import MapReduceJob, run_sharded
from repro.distributed.collectives import ring_all_gather, hierarchical_psum
from repro.launch.mesh import make_test_mesh
from jax import shard_map

mesh = make_test_mesh()  # (2 data, 4 model)
out = {}

# 1. shard_map mapreduce == sequential (run_sharded returns (result, report))
data = jnp.asarray(np.random.default_rng(0).integers(0, 16, (64,)), jnp.int32)
job = MapReduceJob("wc",
    map_fn=lambda x: jnp.bincount(x, length=16),
    combine_fn=lambda a, b: a + b,
    zero_fn=lambda: jnp.zeros(16, jnp.int32))
got, rep = run_sharded(job, data, mesh, axis="data")
want = jnp.bincount(data, length=16)
out["mapreduce_sharded_ok"] = bool((got == want).all())
out["mapreduce_sharded_report_ok"] = rep.makespan >= 0.0

# 2. ring all-gather == lax.all_gather
x = jnp.arange(8.0).reshape(4, 2)
def body(xs):
    ring = ring_all_gather(xs, "model")
    ref = jax.lax.all_gather(xs, "model").reshape(ring.shape)
    return (jnp.abs(ring - ref) < 1e-6).all()
ok = shard_map(body, mesh=mesh, in_specs=(P("model", None),), out_specs=P(),
               check_vma=False)(x)
out["ring_allgather_ok"] = bool(ok)

# 3. hierarchical psum == flat psum on multipod mesh
mesh2 = make_test_mesh(multi_pod=True)  # pod, data, model
y = jnp.arange(8.0)
def body2(ys):
    h = hierarchical_psum(ys, "data", "pod")
    f = jax.lax.psum(ys, ("pod", "data"))
    return (jnp.abs(h - f) < 1e-6).all()
ok2 = shard_map(body2, mesh=mesh2, in_specs=(P(("pod", "data")),),
                out_specs=P(), check_vma=False)(y)
out["hier_psum_ok"] = bool(ok2)

# 4. int8 quantized psum ~= f32 psum
from repro.optim.compression import psum_int8
g = jnp.asarray(np.random.default_rng(1).standard_normal(16), jnp.float32)
def body3(gs):
    approx = psum_int8(gs, "data")
    exact = jax.lax.psum(gs, "data")
    scale = jnp.max(jnp.abs(exact)) + 1e-9
    return (jnp.abs(approx - exact) / scale < 0.05).all()
ok3 = shard_map(body3, mesh=mesh, in_specs=(P("data"),), out_specs=P(),
                check_vma=False)(g)
out["int8_psum_ok"] = bool(ok3)

print("RESULT" + json.dumps(out))
'''


@pytest.fixture(scope="module")
def dist_results():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    proc = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULT")][0]
    return json.loads(line[len("RESULT"):])


def test_sharded_mapreduce(dist_results):
    assert dist_results["mapreduce_sharded_ok"]


def test_ring_all_gather(dist_results):
    assert dist_results["ring_allgather_ok"]


def test_hierarchical_psum(dist_results):
    assert dist_results["hier_psum_ok"]


def test_int8_quantized_psum(dist_results):
    assert dist_results["int8_psum_ok"]


# ---- host-side fault policy tests (no devices needed) ----

def test_detect_stragglers():
    times = np.array([1.0, 1.1, 0.9, 5.0])
    assert detect_stragglers(times, threshold=2.0) == [3]


def test_restart_policy_elastic_shrink():
    prof = HeterogeneityProfile.homogeneous(4)
    pol = RestartPolicy(max_restarts=2)
    p2 = pol.on_device_loss(prof, 1)
    assert p2.n == 3
    with pytest.raises(RuntimeError):
        pol.on_device_loss(p2, 0), pol.on_device_loss(p2, 0)
        pol.on_device_loss(p2, 0)


def test_straggler_observation_reduces_share():
    prof = HeterogeneityProfile.homogeneous(4, 10.0)
    pol = RestartPolicy()
    p2 = pol.on_straggler(prof, 2, slowdown=8.0)
    assert p2.speeds[2] < 10.0


def test_fault_plan_lookup():
    fp = FaultPlan([FaultEvent(3, "device_loss", 1),
                    FaultEvent(3, "straggler", 0, 2.0)])
    assert len(fp.at(3)) == 2 and fp.at(4) == []

"""Trip-count-aware HLO cost model: exactness on known programs."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.launch.hlo_cost import analyze
from repro.launch.roofline import parse_collectives


W = jnp.zeros((128, 128), jnp.float32)


def _cost(f, *args):
    txt = jax.jit(f).lower(*args).compile().as_text()
    return analyze(txt)


def test_unrolled_matmul_flops_exact():
    def f(x):
        for _ in range(10):
            x = x @ W
        return x
    c = _cost(f, jax.ShapeDtypeStruct((128, 128), jnp.float32))
    assert c.flops == pytest.approx(10 * 2 * 128 ** 3)


def test_scan_trip_count_multiplied():
    def f(x):
        return jax.lax.scan(lambda c, _: (c @ W, None), x, None, length=10)[0]
    c = _cost(f, jax.ShapeDtypeStruct((128, 128), jnp.float32))
    assert c.flops == pytest.approx(10 * 2 * 128 ** 3)
    assert c.unknown_trip_loops == 0


def test_nested_scans_multiply():
    def f(x):
        def outer(c, _):
            d = jax.lax.scan(lambda e, _: (e @ W, None), c, None, length=5)[0]
            return d, None
        return jax.lax.scan(outer, x, None, length=4)[0]
    c = _cost(f, jax.ShapeDtypeStruct((128, 128), jnp.float32))
    assert c.flops == pytest.approx(20 * 2 * 128 ** 3)


def test_traffic_nonzero_and_scales_with_trips():
    def f1(x):
        return jax.lax.scan(lambda c, _: (c @ W, None), x, None, length=2)[0]
    def f2(x):
        return jax.lax.scan(lambda c, _: (c @ W, None), x, None, length=20)[0]
    s = jax.ShapeDtypeStruct((128, 128), jnp.float32)
    c1, c2 = _cost(f1, s), _cost(f2, s)
    assert c2.traffic_bytes > 5 * c1.traffic_bytes


def test_collective_parse_on_sharded_program():
    import subprocess, sys, os, json
    script = r'''
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, json
from jax.sharding import PartitionSpec as P, NamedSharding
from repro.launch.hlo_cost import analyze
from repro.distributed.meshes import make_mesh
mesh = make_mesh((2, 4), ("data", "model"))
def f(x, w):
    return jnp.sum(x @ w)
g = jax.grad(f, argnums=1)
sh = lambda *s: NamedSharding(mesh, P(*s))
low = jax.jit(g, in_shardings=(sh("data", None), sh(None, "model"))).lower(
    jax.ShapeDtypeStruct((32, 64), jnp.float32),
    jax.ShapeDtypeStruct((64, 128), jnp.float32))
c = analyze(low.compile().as_text())
print("RESULT" + json.dumps({"coll": c.collective_bytes}))
'''
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    proc = subprocess.run([sys.executable, "-c", script], env=env,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULT")][0]
    assert json.loads(line[6:])["coll"] > 0

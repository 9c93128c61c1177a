"""LCMP as the cross-pod collective scheduler: run a sharded train step
where gradient buckets are LCMP-routed over candidate route programs,
then fail a route and watch the lazy re-bind (fast-failover).

A CPU demo: it runs in a subprocess on 8 virtual CPU devices (2 pods x
2 data x 2 model), so the parent never initializes JAX.

  PYTHONPATH=src python examples/multipod_grad_routes.py
"""
import os
import subprocess
import sys

SCRIPT = r'''
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from jax import shard_map
from jax.sharding import PartitionSpec as P
from repro.dist import lcmp_collectives as lc

mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"))
grads = {f"bucket{i}": jnp.ones((2, 256)) * (i + 1) for i in range(6)}

ids = lc._fmix32_host(np.arange(1, 7, dtype=np.uint32))
print("route binding (all alive):", lc.schedule_buckets(ids))

def reduce_fn(g):
    return lc.lcmp_pod_reduce(g, "pod")
f = shard_map(reduce_fn, mesh=mesh, in_specs=P("pod"), out_specs=P("pod"),
              check_vma=False)
out = jax.jit(f)(jax.tree.map(lambda x: x, grads))
out = jax.tree.map(np.asarray, out)
print("reduced ok:", all(bool((v == v[0, 0]).all()) for v in out.values()))

# kill route 0 (telemetry marks the direct all-reduce path dead)
lc.set_route_liveness([False, True, True])
print("route binding (route0 dead):", lc.schedule_buckets(ids))
'''
env = dict(os.environ, PYTHONPATH="src", JAX_PLATFORMS="cpu")
subprocess.run([sys.executable, "-c", SCRIPT], env=env, check=True)
print("multipod_grad_routes OK")

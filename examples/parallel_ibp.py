"""Distributed IBP inference over a real JAX mesh (shard_map + psum).

On the CPU it relaunches itself with 8 forced host devices; on a TPU it
uses the chips it has. It builds a ('data',) mesh with one shard per
device and runs the hybrid sampler with X and Z physically sharded
across devices — the production code path.

    PYTHONPATH=src python examples/parallel_ibp.py
"""
import os
import sys

import jax
import jax.numpy as jnp

if jax.default_backend() == "cpu" and "XLA_FLAGS" not in os.environ:
    # relaunch with 8 virtual devices
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    os.execv(sys.executable, [sys.executable] + sys.argv)

from repro.core.ibp import IBPHypers, SamplerSpec, build_sampler
from repro.core.ibp.diagnostics import train_joint_loglik
from repro.data import cambridge_data

N, Pn, K_max, K_tail = 320, jax.device_count(), 16, 6
print(f"devices: {jax.device_count()} | observations: {N} over P={Pn} shards")

X, _, _ = cambridge_data(N=N, sigma_n=0.5, seed=1)

# data="shardmap" puts X and Z physically on a ('data',) mesh of Pn
# devices; build_sampler owns mesh construction and data placement
spec = SamplerSpec(P=Pn, K_max=K_max, K_tail=K_tail, K_init=3, L=5,
                   data="shardmap")
sampler = build_sampler(spec, IBPHypers(), X)
gs, st = sampler.init(jax.random.key(1))

for it in range(60):
    gs, st = sampler.step(gs, st)
    # serialize dispatch: 8 virtual devices share one core here, and
    # letting iterations queue up can starve the collective rendezvous
    jax.block_until_ready(st[0])
    if (it + 1) % 20 == 0:
        Zf = st[0]
        ll = train_joint_loglik(jnp.asarray(sampler.X_global), Zf, gs.A,
                                gs.pi, gs.active, gs.sigma_x)
        print(f"iter {it + 1:3d}: K+ = {int(gs.active.sum())}, "
              f"p' = shard {int(gs.p_prime)}, "
              f"log P(X,Z) = {float(ll):.1f}")

# Z really is distributed: one shard per device
Zf = st[0]
assert len(Zf.sharding.device_set) == Pn

K = int(gs.active.sum())
assert 3 <= K <= 9, K
print(f"\nOK — converged to K+ = {K} features with Z sharded on "
      f"{len(Zf.sharding.device_set)} devices")

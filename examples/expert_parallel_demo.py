"""Expert parallelism demo on 8 simulated devices.

Shows the padding-free MoE layer running under shard_map with experts
sharded 8-ways, verifying EP output == single-device output, and printing
the collectives XLA emitted.

  PYTHONPATH=src python examples/expert_parallel_demo.py
"""
import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=8")

import re

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.launch.mesh import make_mesh
from repro.core.moe import (MoEConfig, init_moe_params, moe_apply,
                            shard_moe_params)


def main():
    assert len(jax.devices()) >= 8
    mesh = make_mesh((2, 4), ("data", "model"))
    cfg = MoEConfig(num_experts=8, top_k=2, d_model=256, d_ff_expert=128,
                    num_shared_experts=1, capacity_factor=8.0)
    params = init_moe_params(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (16, 128, 256))

    # single-device reference
    y_ref, aux = moe_apply(params, x.reshape(-1, 256), cfg)
    y_ref = y_ref.reshape(x.shape)

    ep = 4  # experts 8 / model axis 4 -> 2 experts per shard
    pspecs = shard_moe_params(params, cfg, ep)
    xspec = P("data", None, None)

    def local_fn(p, xl):
        rank = jax.lax.axis_index("model")
        b, s, d = xl.shape
        y, aux = moe_apply(p, xl.reshape(b * s, d), cfg, ep_rank=rank,
                           ep_size=ep, axis_name="model")
        return y.reshape(b, s, d)

    fn = jax.jit(jax.shard_map(local_fn, mesh=mesh,
                               in_specs=(pspecs, xspec), out_specs=xspec,
                               check_vma=False))
    y_ep = fn(params, x)

    err = float(jnp.max(jnp.abs(y_ep - y_ref)))
    rel = err / max(float(jnp.max(jnp.abs(y_ref))), 1e-6)
    print(f"EP(4-way) vs single-device max |err|: {err:.2e} (rel {rel:.2e})")
    # relative criterion: the EP reduction reassociates bf16 partial sums,
    # so the tolerable absolute error scales with the output magnitude
    assert rel < 1e-3

    hlo = fn.lower(params, x).compile().as_text()
    colls = re.findall(r"(all-reduce|all-gather|reduce-scatter|"
                       r"all-to-all|collective-permute)\(", hlo)
    from collections import Counter
    print("collectives emitted:", dict(Counter(colls)))
    print("OK: padding-free MoE is EP-sharded and numerically faithful")


if __name__ == "__main__":
    main()

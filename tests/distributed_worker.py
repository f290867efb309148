"""Worker for test_jax_distributed_two_process — each process joins a real
jax.distributed coordination service (the NCCL2-bootstrap analog,
reference imperative/nccl_context.cc:22-134), forms a GLOBAL mesh spanning
both processes' CPU devices, and runs the framework's c_allreduce_sum
kernel across the process boundary."""
import json
import os
import sys

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
os.environ["JAX_PLATFORMS"] = "cpu"
sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import jax

jax.config.update("jax_platforms", "cpu")


def main():
    port, rank, out_dir = sys.argv[1], int(sys.argv[2]), sys.argv[3]
    jax.distributed.initialize(
        coordinator_address=f"127.0.0.1:{port}",
        num_processes=2, process_id=rank)

    import numpy as np
    import jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec as P, NamedSharding
    from paddle_tpu.ops.registry import run_kernel, OpContext

    devs = np.array(jax.devices())          # 4 global (2 per process)
    assert devs.size == 4, devs
    mesh = Mesh(devs, ("dp",))
    ctx = OpContext(mesh_axes=("dp",), dist_info={0: "dp"})

    def step(x):
        return run_kernel("c_allreduce_sum", {"X": x},
                          {"ring_id": 0, "use_calc_stream": True},
                          ctx)["Out"]

    fn = jax.jit(jax.shard_map(step, mesh=mesh, in_specs=P("dp"),
                           out_specs=P("dp")))
    # per-device shard value = global shard index + 1 -> allreduce sum
    # over 4 shards = 1+2+3+4 = 10 everywhere
    sharding = NamedSharding(mesh, P("dp"))
    local = np.stack([
        np.full((3,), rank * 2 + 1, np.float32),
        np.full((3,), rank * 2 + 2, np.float32)])
    garr = jax.make_array_from_process_local_data(sharding, local, (4, 3))
    out = fn(garr)
    vals = sorted(float(np.asarray(s.data).ravel()[0])
                  for s in out.addressable_shards)
    with open(os.path.join(out_dir, f"allreduce_rank{rank}.json"),
              "w") as f:
        json.dump({"rank": rank, "shard_values": vals,
                   "n_global_devices": int(devs.size)}, f)
    jax.distributed.shutdown()


if __name__ == "__main__":
    main()

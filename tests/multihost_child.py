"""Child process for the two-process multi-host feed test.

Invoked by tests/test_multihost.py as
    python multihost_child.py <coordinator> <num_procs> <proc_id>
with JAX_PLATFORMS=cpu and --xla_force_host_platform_device_count=4, so the
pair of processes forms a 2-host x 4-device cluster — the JAX analogue of the
reference's gloo multi-process dataset harness
(/root/reference/src/dataset.py:431-506).

Asserts, from inside each process:
  1. jax.distributed wires 2 processes into one 8-device platform.
  2. HostShardSampler gives each host its contiguous global chunk.
  3. make_array_from_process_local_data (parallel/mesh.host_to_device_batch)
     lands each host's chunk in the right global shard — verified by
     allgathering the assembled global array and comparing to the exact
     expected global ordering.
  4. A jitted psum over the mesh sees every host's data exactly once.
  5. Mid-epoch state_dict/load_state_dict resume continues the stream.
  6. (argv[4] = shared dir) orbax CheckpointManager saves a sharded pytree
     with cross-process coordination and restores it sharded — the path
     run_pretraining relies on for pod-scale checkpointing, which only works
     when jax.distributed is initialized (parallel/dist.initialize).
  7. Multi-host metrics aggregation (telemetry/multihost.py): both processes
     publish per-host StepWatch-style records into the shared dir; process 0
     folds cross-host min/mean/max step time + data_wait and flags the slow
     host as a straggler — the wiring run_pretraining enables via
     init_run(multihost_dir=...) when process_count > 1.
"""

import os
import sys

import numpy as np


def main() -> None:
    coordinator, num_procs, proc_id = (
        sys.argv[1], int(sys.argv[2]), int(sys.argv[3]))
    ckpt_dir = sys.argv[4] if len(sys.argv) > 4 else None

    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax

    jax.config.update("jax_cpu_collectives_implementation", "gloo")
    jax.distributed.initialize(coordinator_address=coordinator,
                               num_processes=num_procs,
                               process_id=proc_id)

    assert jax.process_count() == num_procs, jax.process_count()
    assert jax.process_index() == proc_id
    assert jax.local_device_count() == 4, jax.local_device_count()
    assert jax.device_count() == 4 * num_procs, jax.device_count()

    import jax.numpy as jnp
    from jax.experimental import multihost_utils

    from bert_pytorch_tpu.data.sharded import HostShardSampler
    from bert_pytorch_tpu.parallel import mesh as mesh_lib

    mesh = mesh_lib.make_mesh({"fsdp": 2})  # data=4 absorbed, fsdp=2 -> 8 way

    dataset_size = 32
    sampler = HostShardSampler(dataset_size, world_size=num_procs,
                               rank=jax.process_index())
    assert sampler.num_samples == 16

    # --- per-host chunk math -------------------------------------------------
    per_host_batch = 8
    idx = sampler.next_indices(per_host_batch)
    expected = np.arange(proc_id * 16, proc_id * 16 + 8) % dataset_size
    np.testing.assert_array_equal(idx, expected)

    # --- host feed seam: local chunk -> correct global shard -----------------
    batch = mesh_lib.host_to_device_batch(
        mesh, {"x": idx.astype(np.int32)}, stacked=False)
    global_x = batch["x"]
    assert global_x.shape == (per_host_batch * num_procs,)
    gathered = np.asarray(
        multihost_utils.process_allgather(global_x, tiled=True))
    # global order must be host0's chunk then host1's chunk — exactly the
    # contiguous per-rank layout the reference's DistributedSampler produced
    want_global = np.concatenate(
        [np.arange(r * 16, r * 16 + 8) for r in range(num_procs)])
    np.testing.assert_array_equal(gathered, want_global)

    # --- a compiled reduction sees every host's data exactly once ------------
    total = jax.jit(jnp.sum, out_shardings=None)(global_x)
    assert int(total) == int(want_global.sum()), (int(total), want_global.sum())

    # --- mid-epoch resume ----------------------------------------------------
    state = sampler.state_dict()
    idx2_a = sampler.next_indices(per_host_batch)
    fresh = HostShardSampler(dataset_size, world_size=num_procs,
                             rank=jax.process_index())
    fresh.load_state_dict(state)
    idx2_b = fresh.next_indices(per_host_batch)
    np.testing.assert_array_equal(idx2_a, idx2_b)
    assert fresh.next_indices(per_host_batch) is None  # epoch exhausted

    # --- cross-host metrics fold + straggler detection -----------------------
    if ckpt_dir is not None:
        from bert_pytorch_tpu.telemetry.multihost import \
            HostMetricsAggregator

        # process 1 publishes a 3x slower step; with 2 hosts the worst
        # z-score is exactly 1.0, so threshold 0.5 must flag it
        mdir = os.path.join(os.path.dirname(ckpt_dir), "metrics_hosts")
        agg = HostMetricsAggregator(mdir, process_index=proc_id,
                                    process_count=num_procs,
                                    z_threshold=0.5)
        agg.publish(7, {"step_time_ms": 100.0 * (1 + 2 * proc_id),
                        "data_wait_ms": 1.0 + proc_id,
                        "seq_per_sec": 8.0})
        multihost_utils.sync_global_devices("metrics_published")
        if proc_id == 0:
            folded, warning = agg.fold()
            assert folded["hosts_reporting"] == num_procs, folded
            assert folded["hosts_step_min"] == 7
            assert folded["step_time_ms_host_min"] == 100.0
            assert folded["step_time_ms_host_max"] == 300.0
            assert folded["step_time_ms_host_mean"] == 200.0
            assert folded["data_wait_ms_host_max"] == 2.0
            assert folded["straggler_host"] == 1, folded
            assert warning is not None and "host 1" in warning, warning
        agg.close()
        multihost_utils.sync_global_devices("metrics_folded")

    # --- cross-process sharded checkpoint save + restore ---------------------
    if ckpt_dir is not None:
        from jax.sharding import NamedSharding, PartitionSpec as P

        from bert_pytorch_tpu.training.checkpoint import CheckpointManager

        sharded = NamedSharding(mesh, P(("data", "fsdp")))
        state = {
            "w": jax.device_put(jnp.arange(64, dtype=jnp.float32), sharded),
            "step": jax.device_put(jnp.asarray(7, jnp.int32),
                                   NamedSharding(mesh, P())),
        }
        mgr = CheckpointManager(ckpt_dir, max_to_keep=2)
        assert mgr.save(7, state, extra={"sampler_index": 16, "epoch": 0})
        mgr.wait()

        abstract = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=a.sharding), state)
        restored, extra, step = mgr.restore(abstract)
        assert step == 7
        assert extra == {"sampler_index": 16, "epoch": 0}, extra
        assert restored["w"].sharding == sharded
        got = np.asarray(
            multihost_utils.process_allgather(restored["w"], tiled=True))
        np.testing.assert_array_equal(got, np.arange(64, dtype=np.float32))
        assert int(restored["step"]) == 7
        mgr.close()

    print(f"MULTIHOST_CHILD_OK proc={proc_id}")


if __name__ == "__main__":
    main()

"""Multi-host Train: a global JAX mesh across real worker PROCESSES.

The seam the reference leaves to torch (``train/torch/config.py:64-100``
NCCL process groups) done TPU-natively: two worker processes on the
multiprocess cluster each own 4 virtual CPU devices, form ONE 8-device
global mesh via ``jax.distributed`` (coordinator address flowing through
the GCS KV — the control plane), and run the full sharded GPT-2-tiny train
step with data parallelism across the process boundary. Losses over two
steps must match a single-process 8-device oracle, which also proves the
gradient psum crossed processes correctly (step 2's loss depends on step
1's update).
"""

import os
import sys

import cloudpickle
import numpy as np

import ray_tpu
from ray_tpu.core.cluster import Cluster, connect
from ray_tpu.core import runtime as runtime_mod

# Worker processes cannot import the tests package — ship this module's
# classes by value (what cloudpickle does automatically for __main__).
cloudpickle.register_pickle_by_value(sys.modules[__name__])

VOCAB, SEQ, GLOBAL_BATCH = 256, 64, 8


class TrainWorker:
    """One per-host training process (4 local devices, rank in a world of 2).

    Device-count/platform env arrives via ``runtime_env={"env_vars": ...}``
    — applied by the node daemon at process SPAWN, before any import of
    jax in the worker can read the wrong config.
    """

    def __init__(self, rank: int, world: int):
        self.rank = rank
        self.world = world

    def reserve_coordinator(self) -> str:
        """Rank 0: pick a free port; the driver publishes it via GCS KV."""
        import socket

        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
        s.close()
        return f"127.0.0.1:{port}"

    def init_distributed(self, coordinator: str) -> int:
        import jax

        jax.distributed.initialize(
            coordinator_address=coordinator,
            num_processes=self.world,
            process_id=self.rank,
        )
        jax.config.update("jax_default_matmul_precision", "highest")
        return len(jax.devices())  # global device count

    def train_two_steps(self, tokens_local: np.ndarray):
        """Run two sharded train steps; returns both global losses."""
        import jax
        import jax.numpy as jnp
        import optax

        from ray_tpu.models import transformer
        from ray_tpu.models.training import make_train_step
        from ray_tpu.parallel.mesh import MeshSpec, make_mesh
        from ray_tpu.parallel.sharding import ShardingRules

        mesh = make_mesh(MeshSpec(data=-1), devices=jax.devices())
        rules = ShardingRules()
        cfg = transformer.tiny(
            vocab_size=VOCAB, d_model=32, n_layers=2, n_heads=2, d_ff=64,
            max_seq_len=SEQ, vocab_multiple=128, attn_impl="dense",
            dtype=jnp.float32, param_dtype=jnp.float32,
        )
        bundle = make_train_step(
            loss_fn=lambda p, b: transformer.lm_loss(p, b, cfg, mesh=mesh, rules=rules),
            init_params_fn=lambda k: transformer.init_params(cfg, k),
            logical_params=transformer.logical_axes(cfg),
            mesh=mesh, rules=rules,
            optimizer=optax.adamw(1e-2),
            batch_logical=("batch", None),
        )
        params, opt_state = bundle.init(jax.random.key(0))
        # Each process contributes its local half of the global batch.
        batch = {"tokens": jax.make_array_from_process_local_data(
            bundle.batch_sharding, tokens_local)}
        losses = []
        for _ in range(2):
            params, opt_state, metrics = bundle.step(params, opt_state, batch)
            # loss is fully replicated — locally addressable on every process
            losses.append(float(metrics["loss"]))
        return losses


def _oracle_two_steps(tokens_global: np.ndarray):
    """Single-process 8-device oracle (the pytest process's CPU mesh)."""
    import jax
    import jax.numpy as jnp
    import optax

    from ray_tpu.models import transformer
    from ray_tpu.models.training import make_train_step
    from ray_tpu.parallel.mesh import MeshSpec, make_mesh
    from ray_tpu.parallel.sharding import ShardingRules

    devices = jax.devices("cpu")
    mesh = make_mesh(MeshSpec(data=-1), devices=devices)
    rules = ShardingRules()
    cfg = transformer.tiny(
        vocab_size=VOCAB, d_model=32, n_layers=2, n_heads=2, d_ff=64,
        max_seq_len=SEQ, vocab_multiple=128, attn_impl="dense",
        dtype=jnp.float32, param_dtype=jnp.float32,
    )
    bundle = make_train_step(
        loss_fn=lambda p, b: transformer.lm_loss(p, b, cfg, mesh=mesh, rules=rules),
        init_params_fn=lambda k: transformer.init_params(cfg, k),
        logical_params=transformer.logical_axes(cfg),
        mesh=mesh, rules=rules,
        optimizer=optax.adamw(1e-2),
        batch_logical=("batch", None),
    )
    params, opt_state = bundle.init(jax.random.key(0))
    batch = {"tokens": jax.device_put(jnp.asarray(tokens_global),
                                      bundle.batch_sharding)}
    losses = []
    for _ in range(2):
        params, opt_state, metrics = bundle.step(params, opt_state, batch)
        losses.append(float(metrics["loss"]))
    return losses


def test_two_process_global_mesh_matches_oracle():
    rng = np.random.default_rng(0)
    tokens = rng.integers(0, VOCAB, (GLOBAL_BATCH, SEQ)).astype(np.int32)

    oracle = _oracle_two_steps(tokens)

    cluster = Cluster(num_nodes=2, resources_per_node={"CPU": 4})
    try:
        core = connect(cluster.gcs_address)
        try:
            worker_cls = ray_tpu.remote(TrainWorker)
            env_vars = {
                "JAX_PLATFORMS": "cpu",
                "JAX_NUM_CPU_DEVICES": "4",
                "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
            }
            workers = [
                worker_cls.options(
                    num_cpus=2, runtime_env={"env_vars": env_vars}
                ).remote(r, 2)
                for r in range(2)
            ]
            # Coordinator address flows through the control plane: rank 0
            # reserves it, the driver publishes to the GCS KV, rank 1 reads
            # it back (the reference broadcasts rank 0's addr the same way).
            coordinator = ray_tpu.get(workers[0].reserve_coordinator.remote(),
                                      timeout=120)
            core.gcs.kv_put("train/coordinator", coordinator.encode())
            addr = core.gcs.kv_get("train/coordinator").decode()
            # Both inits must be in flight together (the service blocks
            # until every process connects).
            counts = ray_tpu.get(
                [w.init_distributed.remote(addr) for w in workers],
                timeout=300)
            assert counts == [8, 8], f"global mesh wrong: {counts}"

            halves = [tokens[:GLOBAL_BATCH // 2], tokens[GLOBAL_BATCH // 2:]]
            refs = [w.train_two_steps.remote(h)
                    for w, h in zip(workers, halves)]
            losses = ray_tpu.get(refs, timeout=300)
            # Every process observed the same (replicated) global loss...
            np.testing.assert_allclose(losses[0], losses[1], rtol=1e-6)
            # ...and it matches the single-process oracle across BOTH steps
            # (step 2 proves the cross-process gradient psum was applied).
            np.testing.assert_allclose(losses[0], oracle, rtol=2e-4, atol=2e-4)
        finally:
            core.shutdown()
            runtime_mod._global_runtime = None
    finally:
        cluster.shutdown()


# ---------------------------------------------------------------------------
# Elastic fault tolerance (SURVEY hard-part #4; reference answer: whole-group
# restart from the last checkpoint, backend_executor.py:121 + FailureConfig)
# ---------------------------------------------------------------------------


def _elastic_loop(config):
    """Deterministic 'training': loss halves each step. Rank 1 kills its own
    PROCESS (kill -9 semantics: no cleanup, no finish() report) at step 3 of
    the FIRST incarnation; the restarted group must resume from the last
    checkpoint, not step 0."""
    import os

    from ray_tpu import train as rt_train

    ctx = rt_train.get_context()
    start_step, loss = 0, 64.0
    ckpt = rt_train.get_checkpoint()
    if ckpt is not None:
        state = ckpt.to_dict()
        start_step, loss = int(state["step"]) + 1, float(state["loss"])

    marker = config["marker"]
    for step in range(start_step, config["steps"]):
        loss = loss / 2.0
        if (ctx.get_world_rank() == 1 and step == 3
                and not os.path.exists(marker)):
            open(marker, "w").close()
            os._exit(1)  # hard死 — simulates a host/process loss
        rt_train.report(
            {"step": step, "loss": loss, "rank": ctx.get_world_rank()},
            checkpoint=(rt_train.Checkpoint.from_dict(
                {"step": step, "loss": loss})
                if ctx.get_world_rank() == 0 else None),
        )


def test_elastic_worker_death_restores_and_resumes(tmp_path):
    """Kill one worker process mid-training: the BackendExecutor detects the
    death (no hang on the round barrier), fit() tears the group down,
    restarts it, restores the last checkpoint, and the loss trajectory
    CONTINUES (values prove resume-from-checkpoint, not restart-from-0)."""
    from ray_tpu.train import (FailureConfig, JaxTrainer, RunConfig,
                               ScalingConfig)

    cluster = Cluster(num_nodes=2, resources_per_node={"CPU": 2})
    try:
        core = connect(cluster.gcs_address)
        try:
            marker = str(tmp_path / "killed-once")
            trainer = JaxTrainer(
                _elastic_loop,
                train_loop_config={"steps": 6, "marker": marker},
                scaling_config=ScalingConfig(num_workers=2,
                                             cpus_per_worker=1),
                run_config=RunConfig(
                    name="elastic",
                    storage_path=str(tmp_path / "results"),
                    failure_config=FailureConfig(max_failures=2),
                ),
            )
            result = trainer.fit()
            assert result.error is None, result.error
            losses = [m["loss"] for m in result.metrics_history]
            # Deterministic halving from 64.0: a restart-from-scratch would
            # repeat the early values; resume continues the series. The
            # kill at step 3 may or may not lose step 2/3's report, so
            # check: monotone halving, last value correct, and the series
            # NEVER rewinds upward (which restart-from-0 would do).
            assert losses[-1] == 64.0 / 2 ** 6, losses
            assert all(b < a for a, b in zip(losses, losses[1:])), losses
            assert os.path.exists(marker), "kill never happened"
        finally:
            core.shutdown()
            runtime_mod._global_runtime = None
    finally:
        cluster.shutdown()


def test_elastic_jax_distributed_world_reforms(tmp_path):
    """After a worker-process death, the restarted group re-forms the
    jax.distributed world (fresh coordinator, full device count) and a
    cross-process psum still produces the right value — XLA's fixed-world
    assumption handled by whole-group restart."""
    from ray_tpu.train import (FailureConfig, JaxConfig, JaxTrainer,
                               RunConfig, ScalingConfig)

    def loop(config):
        import os

        import jax
        import jax.numpy as jnp

        from ray_tpu import train as rt_train

        ctx = rt_train.get_context()
        if (ctx.get_world_rank() == 1
                and not os.path.exists(config["marker"])):
            open(config["marker"], "w").close()
            os._exit(1)
        n_global = len(jax.devices())
        # psum across the whole re-formed world
        from ray_tpu.parallel.mesh import MeshSpec, make_mesh

        mesh = make_mesh(MeshSpec(data=-1), devices=jax.devices())
        import numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P

        local = np.full((2,), float(ctx.get_world_rank() + 1))
        arr = jax.make_array_from_process_local_data(
            NamedSharding(mesh, P("data")), local)
        total = jax.jit(
            lambda x: jax.numpy.sum(x),
            out_shardings=NamedSharding(mesh, P()))(arr)
        rt_train.report({"devices": n_global, "total": float(total),
                         "incarnation": 2})

    cluster = Cluster(num_nodes=2, resources_per_node={"CPU": 2})
    try:
        core = connect(cluster.gcs_address)
        try:
            marker = str(tmp_path / "jx-killed-once")
            env_vars = {
                "JAX_PLATFORMS": "cpu",
                "JAX_NUM_CPU_DEVICES": "2",
                "XLA_FLAGS": "--xla_force_host_platform_device_count=2",
            }
            trainer = JaxTrainer(
                loop,
                train_loop_config={"marker": marker},
                backend_config=JaxConfig(init_distributed=True),
                scaling_config=ScalingConfig(
                    num_workers=2, cpus_per_worker=1,
                    runtime_env={"env_vars": env_vars}),
                run_config=RunConfig(
                    name="elastic-jax",
                    storage_path=str(tmp_path / "results"),
                    failure_config=FailureConfig(max_failures=2),
                ),
            )
            result = trainer.fit()
            assert result.error is None, result.error
            m = result.metrics
            # world re-formed: 2 procs x 2 devices; psum over per-rank
            # contributions (1+1) + (2+2) = 6
            assert m["devices"] == 4, m
            assert m["total"] == 6.0, m
            assert os.path.exists(marker)
        finally:
            core.shutdown()
            runtime_mod._global_runtime = None
    finally:
        cluster.shutdown()

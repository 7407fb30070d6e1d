"""The train step alone: mesh -> ``make_train_step`` with the model's loss,
adamw, packed sequences from the seed. The timed loop is ``bench.py``'s
method (one process, every step ends in ``block_until_ready``), copied here
so that the yardstick does not import the thing it measures."""

from __future__ import annotations

import time
from typing import Dict, List

from benchmark.drivers import common


def run(ctx: Dict) -> Dict:
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from ray_tpu.models.training import make_train_step
    from ray_tpu.parallel.mesh import MeshSpec, make_mesh
    from ray_tpu.parallel.sharding import ShardingRules

    from benchmark.reference import gpt2_plain as ref

    phases = {"start": time.perf_counter()}
    config, traffic = ctx["config"], ctx["traffic"]
    seconds = float(ctx["seconds"])
    cfg = common.model_config(config, ctx["rehearse"])
    cfg = cfg.replace(**traffic.get("model_overrides", {}))
    chips = int(ctx["chips"])
    devices = jax.devices()[:chips]
    mesh = make_mesh(MeshSpec(**config["deployment"]["mesh"][str(chips)]),
                     devices=devices)
    rules = ShardingRules()
    loss_fn = common.resolve(config["loss"])
    init = common.resolve(config["init"])
    logical = common.resolve(config["logical_axes"])
    opt = traffic["optimizer"]
    bundle = make_train_step(
        loss_fn=lambda p, b: loss_fn(p, b, cfg, mesh=mesh, rules=rules),
        init_params_fn=lambda key: init(cfg, key),
        logical_params=logical(cfg), mesh=mesh, rules=rules,
        optimizer=optax.adamw(float(opt["learning_rate"]),
                              weight_decay=float(opt["weight_decay"])),
        batch_logical=("batch", None))
    per_chip = int(config["deployment"]["train"]["sequences_per_chip"])
    gb, seq = per_chip * chips, int(traffic["sequence_tokens"])
    n_batches = int(traffic["batches"])
    seed = common.seed32(ctx["seed"])

    # Data: packed sequences made on the device in one jitted call from the
    # seed. Token ids are Zipf-like (id = floor(V**u) - 1, so P(id) ~ 1/id):
    # a distribution the model can learn, so that the loss must fall.
    vocab = cfg.vocab_size

    def make_data(key):
        u = jax.random.uniform(key, (n_batches, gb, seq))
        return jnp.clip(jnp.floor(jnp.exp(u * jnp.log(float(vocab)))) - 1,
                        0, vocab - 1).astype(jnp.int32)

    data_sh = jax.sharding.NamedSharding(
        mesh, jax.sharding.PartitionSpec(None, *bundle.batch_sharding.spec))
    data = jax.jit(make_data, out_shardings=data_sh)(
        jax.random.fold_in(jax.random.key(seed), 1))
    batches = [{"tokens": data[i]} for i in range(n_batches)]
    params, opt_state = bundle.init(jax.random.key(seed))
    jax.block_until_ready(params)
    phases["state_and_data"] = time.perf_counter()

    # The reference's loss on the first batch, before any step, in chunks of
    # sequences (equal lengths, so the mean of the chunks' means is the
    # batch's mean). The weights stay sharded as they are; XLA partitions
    # the plain code.
    rc = int(traffic["check"]["reference_chunk_sequences"])
    heads = cfg.n_heads
    ref_loss = jax.jit(lambda p, t: ref.loss(ref.from_program_params(p), t,
                                             heads))
    first = batches[0]["tokens"]
    ref_losses = [float(ref_loss(params, first[i:i + rc]))
                  for i in range(0, gb, rc)]
    reference_loss = float(np.mean(ref_losses))
    phases["reference_loss"] = time.perf_counter()

    # Warm-up: the step compiles on its first call; two more to settle.
    losses: List[float] = []
    for i in range(int(traffic["warmup_steps"])):
        params, opt_state, m = bundle.step(params, opt_state,
                                           batches[i % n_batches])
        losses.append(float(m["loss"]))
    first_loss = losses[0]

    t_open = time.perf_counter()
    tracer = common.trace_window(ctx, t_open, seconds)
    step_s: List[float] = []
    step_loss = []
    i = len(losses)
    t_prev = t_open
    while t_prev - t_open < seconds:
        params, opt_state, m = bundle.step(params, opt_state,
                                           batches[i % n_batches])
        jax.block_until_ready(m["loss"])
        now = time.perf_counter()
        step_s.append(now - t_prev)
        step_loss.append(m["loss"])
        t_prev = now
        i += 1
    t_close = t_prev
    if tracer is not None:
        tracer.join()
    last_loss = float(step_loss[-1])
    tol = float(traffic["check"]["loss_rel_tolerance"])
    rel = abs(first_loss - reference_loss) / abs(reference_loss)
    finite = all(np.isfinite(float(x)) for x in step_loss)
    return {
        "t_open": t_open, "t_close": t_close, "window_s": t_close - t_open,
        "attempted": len(step_s), "failed": 0 if finite else 1,
        "correct_parts": {"first_loss_matches_reference": rel <= tol,
                          "loss_finite": finite,
                          "loss_fell": last_loss < first_loss},
        "check": {"first_loss": first_loss, "reference_loss": reference_loss,
                  "rel_diff": rel, "last_loss": last_loss},
        "step_s": step_s, "tokens_per_step": gb * seq, "chips": chips,
        "global_batch": gb, "sequence_tokens": seq,
        "tracer": tracer, "phases": phases,
    }

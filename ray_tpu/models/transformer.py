"""Decoder-only transformer LM (GPT-2 family), TPU-shaped.

This is the flagship model for the Train north-star (BASELINE.json: GPT-2-124M
tokens/sec/chip). The reference has no model code of its own — it orchestrates
torch models (e.g. ``release/air_tests/air_benchmarks/workloads/``); here the
model is a first-class citizen designed for the MXU:

- params are plain pytrees; blocks are STACKED on a leading ``layers`` dim and
  the forward pass is a single ``lax.scan`` — one compiled block body, weight
  gathers pipelined by XLA, and the natural layout for pipeline parallelism
  (``layers`` → ``pipe`` mesh axis).
- every parameter and activation carries *logical* axis names resolved
  through ``parallel.sharding.ShardingRules`` — the same model runs DP, FSDP,
  megatron TP, sequence-parallel or any mix by swapping the rule table,
  never editing model code.
- compute dtype bf16 with f32 accumulation (matmul ``preferred_element_type``,
  f32 layernorm stats/softmax/loss); params kept in f32 by default (optimizer
  numerics), cast to bf16 at use.
- vocab padded to a multiple of 128 so the logits matmul tiles the MXU.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass, field, replace
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.ops.layers import gelu, layer_norm, linear, rope, softmax_cross_entropy
from ray_tpu.parallel.mesh import Mesh
from ray_tpu.parallel.sharding import ShardingRules, constrain


def pad_vocab(n: int, multiple: int = 128) -> int:
    return ((n + multiple - 1) // multiple) * multiple


@dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 50257
    d_model: int = 768
    n_layers: int = 12
    n_heads: int = 12
    d_ff: int = 3072
    max_seq_len: int = 1024
    dtype: Any = jnp.bfloat16          # activation/compute dtype
    param_dtype: Any = jnp.float32     # storage dtype
    pos: str = "learned"               # "learned" (gpt2) | "rope" (llama-ish)
    tie_embeddings: bool = True
    attn_impl: str = "auto"            # "auto" | "dense" | "flash" | "ring" | "ulysses"
    remat: bool = False                # jax.checkpoint each block (HBM↔FLOPs)
    # remat policy: "full" recomputes everything; "dots" saves matmul outputs
    # and recomputes only cheap elementwise ops (usually faster, more HBM)
    remat_policy: str = "full"         # "full" | "dots"
    vocab_multiple: int = 128

    @property
    def head_dim(self) -> int:
        assert self.d_model % self.n_heads == 0
        return self.d_model // self.n_heads

    @property
    def n_kv_heads(self) -> int:
        """K/V heads a pool row stores: every query head has its own."""
        return self.n_heads

    @property
    def padded_vocab(self) -> int:
        return pad_vocab(self.vocab_size, self.vocab_multiple)

    def replace(self, **kw) -> "TransformerConfig":
        return replace(self, **kw)


def gpt2_small(**kw) -> TransformerConfig:
    """GPT-2 124M."""
    return TransformerConfig(**kw)


def gpt2_medium(**kw) -> TransformerConfig:
    return TransformerConfig(d_model=1024, n_layers=24, n_heads=16, d_ff=4096, **kw)


def gpt2_large(**kw) -> TransformerConfig:
    return TransformerConfig(d_model=1280, n_layers=36, n_heads=20, d_ff=5120, **kw)


def gpt2_xl(**kw) -> TransformerConfig:
    return TransformerConfig(d_model=1600, n_layers=48, n_heads=25, d_ff=6400, **kw)


def tiny(**kw) -> TransformerConfig:
    """Test-sized config (runs in ms on CPU)."""
    defaults = dict(
        vocab_size=256, d_model=64, n_layers=2, n_heads=4, d_ff=128,
        max_seq_len=64, dtype=jnp.float32, vocab_multiple=8,
    )
    defaults.update(kw)
    return TransformerConfig(**defaults)


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------

def init_params(config: TransformerConfig, key: jax.Array) -> Dict:
    """GPT-2 init: normal(0.02), residual projections scaled by 1/sqrt(2N)."""
    c = config
    k = iter(jax.random.split(key, 16))
    dt = c.param_dtype
    std = 0.02
    res_std = std / (2 * c.n_layers) ** 0.5

    def nrm(key, shape, s=std):
        return (jax.random.normal(key, shape, jnp.float32) * s).astype(dt)

    L, D, H, Dh, F, V = c.n_layers, c.d_model, c.n_heads, c.head_dim, c.d_ff, c.padded_vocab

    blocks = {
        "ln1_g": jnp.ones((L, D), dt), "ln1_b": jnp.zeros((L, D), dt),
        "wq": nrm(next(k), (L, D, H, Dh)), "wk": nrm(next(k), (L, D, H, Dh)),
        "wv": nrm(next(k), (L, D, H, Dh)),
        "wo": nrm(next(k), (L, H, Dh, D), res_std),
        "bq": jnp.zeros((L, H, Dh), dt), "bk": jnp.zeros((L, H, Dh), dt),
        "bv": jnp.zeros((L, H, Dh), dt), "bo": jnp.zeros((L, D), dt),
        "ln2_g": jnp.ones((L, D), dt), "ln2_b": jnp.zeros((L, D), dt),
        "w_up": nrm(next(k), (L, D, F)), "b_up": jnp.zeros((L, F), dt),
        "w_down": nrm(next(k), (L, F, D), res_std), "b_down": jnp.zeros((L, D), dt),
    }
    params = {
        "tok_embed": nrm(next(k), (V, D)),
        "blocks": blocks,
        "lnf_g": jnp.ones((D,), dt), "lnf_b": jnp.zeros((D,), dt),
    }
    if c.pos == "learned":
        params["pos_embed"] = nrm(next(k), (c.max_seq_len, D), 0.01)
    if not c.tie_embeddings:
        params["lm_head"] = nrm(next(k), (D, V))
    return params


def logical_axes(config: TransformerConfig) -> Dict:
    """Pytree of logical axis names mirroring ``init_params`` output."""
    c = config
    blocks = {
        "ln1_g": ("layers", "embed"), "ln1_b": ("layers", "embed"),
        "wq": ("layers", "embed", "heads", "head_dim"),
        "wk": ("layers", "embed", "kv_heads", "head_dim"),
        "wv": ("layers", "embed", "kv_heads", "head_dim"),
        "wo": ("layers", "heads", "head_dim", "embed"),
        "bq": ("layers", "heads", "head_dim"), "bk": ("layers", "kv_heads", "head_dim"),
        "bv": ("layers", "kv_heads", "head_dim"), "bo": ("layers", "embed"),
        "ln2_g": ("layers", "embed"), "ln2_b": ("layers", "embed"),
        "w_up": ("layers", "embed", "mlp"), "b_up": ("layers", "mlp"),
        "w_down": ("layers", "mlp", "embed"), "b_down": ("layers", "embed"),
    }
    axes = {
        "tok_embed": ("vocab", "embed"),
        "blocks": blocks,
        "lnf_g": ("embed",), "lnf_b": ("embed",),
    }
    if c.pos == "learned":
        axes["pos_embed"] = (None, "embed")
    if not c.tie_embeddings:
        axes["lm_head"] = ("embed", "vocab")
    return axes


def param_count(params) -> int:
    return sum(int(p.size) for p in jax.tree.leaves(params))


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

def _dense_attention(q, k, v, *, scale: float, cstr=None):
    """Causal full attention in f32. q/k/v: [B, L, H, Dh].

    ``cstr(x, logical)`` (optional) pins intermediate shardings: without
    it, the seq×tensor layout transition around the two einsums makes the
    SPMD partitioner fall back to "involuntary full rematerialization"
    (replicate-then-repartition) on the activation reshapes — a real
    all-to-all's worth of extra traffic on hardware.
    """
    l = q.shape[1]
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32)
    if cstr is not None:
        scores = cstr(scores, ("batch", "heads", "seq_act", None))
    scores = scores * scale
    mask = jnp.tril(jnp.ones((l, l), bool))
    scores = jnp.where(mask[None, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, v.astype(jnp.float32))
    if cstr is not None:
        out = cstr(out, ("batch", "seq_act", "heads", "head_dim"))
    return out.astype(q.dtype)


def _mesh_axes_size(mesh: Mesh, axes) -> int:
    """Number of shards a logical axis' mesh axes (str | tuple | None) cut."""
    names = axes if isinstance(axes, tuple) else (axes,)
    size = 1
    for name in names:
        if name is not None and name in mesh.shape:
            size *= mesh.shape[name]
    return size


def _make_flash_attention(scale: float, mesh: Optional[Mesh],
                          rules: ShardingRules):
    """Causal flash attention over [B, L, H, Dh], per shard under a mesh.

    A Mosaic kernel is one device's program: GSPMD cannot partition it, so
    on a mesh the call is wrapped in ``shard_map`` — batch on the data axes,
    heads on the tensor axis, every shard a full-length causal problem of its
    own. (A sharded SEQUENCE is not: see ``_make_attention``.) The kernel is
    compiled when the devices it runs on are TPUs and interpreted anywhere
    else — read off the mesh when there is one, so a program lowered for a
    device other than the default backend's gets the right kernel."""
    from ray_tpu.ops.flash_attention import flash_attention

    platform = (mesh.devices.flat[0].platform if mesh is not None
                else jax.default_backend())
    interpret = platform != "tpu"

    def flash(q, k, v):
        # The kernel's own block rule picks the blocks (ops/flash_attention:
        # ``_blocks``) for any multiple of 128, so lengths like 1280 run it;
        # the bound 128 makes any other length raise inside it.
        l = q.shape[1]
        blk = l if l % 128 == 0 else 128
        return flash_attention(q, k, v, True, scale, blk, blk, interpret)

    if mesh is None:
        return flash
    q_spec = rules.mesh_axes(("batch", None, "heads", None))
    kv_spec = rules.mesh_axes(("batch", None, "kv_heads", None))
    return jax.shard_map(flash, mesh=mesh,
                         in_specs=(q_spec, kv_spec, kv_spec),
                         out_specs=q_spec, check_vma=False)


def _make_attention(config: TransformerConfig, mesh: Optional[Mesh],
                    rules: Optional[ShardingRules] = None):
    scale = 1.0 / config.head_dim ** 0.5
    impl = config.attn_impl
    if impl not in ("auto", "dense", "flash", "ring", "ulysses"):
        raise ValueError(f"unknown attn_impl {config.attn_impl!r}")
    axes = rules or ShardingRules()
    seq_shards = (_mesh_axes_size(mesh, axes.seq_act)
                  if mesh is not None else 1)
    if seq_shards > 1:
        # Per-shard flash over a sharded sequence would mask each block as
        # if it started at position 0 — wrong causality, silently.
        if impl == "flash":
            raise ValueError(
                f"attn_impl='flash' cannot run with the sequence sharded "
                f"{seq_shards} ways; use 'ring' or 'ulysses' (or 'auto')")
        if impl == "auto":
            impl = "ring"

    if mesh is not None and rules is not None:
        dense = functools.partial(
            _dense_attention, scale=scale,
            cstr=lambda x, logical: constrain(x, mesh, rules, logical))
    else:
        dense = functools.partial(_dense_attention, scale=scale)

    if impl in ("auto", "flash"):
        flash = _make_flash_attention(scale, mesh, axes)
        if impl == "flash":
            return flash

        def auto(q, k, v):
            # Flash from 1k tokens up; below that, or for a length the
            # kernel's blocks do not divide, the dense path. Decided on the
            # traced length, which is static. (The 1k threshold predates
            # this installation and has not been re-measured: ROADMAP S1.)
            l = q.shape[1]
            return (flash if l >= 1024 and l % 128 == 0 else dense)(q, k, v)

        return auto
    if impl == "dense" or mesh is None:
        return dense
    if impl == "ring":
        from ray_tpu.parallel.ring_attention import make_ring_attention

        return make_ring_attention(mesh, causal=True, scale=scale)
    from ray_tpu.parallel.ring_attention import make_ulysses_attention

    return make_ulysses_attention(mesh, causal=True, scale=scale)


def make_block_fn(
    config: TransformerConfig,
    mesh: Optional[Mesh] = None,
    rules: Optional[ShardingRules] = None,
):
    """One transformer block as ``block(h, bp) -> h`` — THE layer body,
    shared by the scan-over-layers forward and the pipeline-parallel path
    (same math ⇒ PP losses match the non-PP oracle exactly). Sharding
    constraints no-op when mesh/rules are None (required inside shard_map,
    where per-device code cannot carry global sharding annotations)."""
    c = config
    cast = lambda p: p.astype(c.dtype)
    attention = _make_attention(c, mesh, rules)

    def cstr(x, logical):
        if mesh is not None and rules is not None:
            return constrain(x, mesh, rules, logical)
        return x

    def block(h, bp):
        positions = jnp.arange(h.shape[1])
        bp = jax.tree.map(cast, bp)
        x = layer_norm(h, bp["ln1_g"], bp["ln1_b"])
        q = jnp.einsum("bld,dhk->blhk", x, bp["wq"], preferred_element_type=jnp.float32).astype(c.dtype) + bp["bq"]
        kk = jnp.einsum("bld,dhk->blhk", x, bp["wk"], preferred_element_type=jnp.float32).astype(c.dtype) + bp["bk"]
        vv = jnp.einsum("bld,dhk->blhk", x, bp["wv"], preferred_element_type=jnp.float32).astype(c.dtype) + bp["bv"]
        if c.pos == "rope":
            q = rope(q, positions)
            kk = rope(kk, positions)
        q = cstr(q, ("batch", "seq_act", "heads", "head_dim"))
        kk = cstr(kk, ("batch", "seq_act", "kv_heads", "head_dim"))
        vv = cstr(vv, ("batch", "seq_act", "kv_heads", "head_dim"))
        o = attention(q, kk, vv)
        o = jnp.einsum("blhk,hkd->bld", o, bp["wo"], preferred_element_type=jnp.float32).astype(c.dtype) + bp["bo"]
        h = cstr(h + o, ("batch", "seq_act", None))

        x = layer_norm(h, bp["ln2_g"], bp["ln2_b"])
        u = linear(x, bp["w_up"], bp["b_up"])
        u = cstr(gelu(u), ("batch", "seq_act", "mlp"))
        d = linear(u, bp["w_down"], bp["b_down"])
        h = cstr(h + d, ("batch", "seq_act", None))
        return h

    return block


def make_tp_block_fn(config: TransformerConfig, mesh: Mesh,
                     rules: ShardingRules):
    """Per-DEVICE transformer block for use INSIDE ``shard_map`` (the
    pipeline body): tensor parallelism and sequence parallelism are written
    as explicit collectives instead of sharding constraints —

    - megatron TP: q/k/v/up projections are column-parallel (weights arrive
      with heads/mlp dims locally sliced), out/down projections are
      row-parallel with a ``lax.psum`` over the tensor axis before the
      (replicated) bias — the pattern §2.4 says the reference only reaches
      by delegating to DeepSpeed;
    - SP: ring attention over the seq axis (K/V blocks rotate via
      ``ppermute``, online softmax — parallel.ring_attention), with RoPE
      positions offset by the device's sequence block.

    With tensor=1 and seq=1 this degrades to exactly the plain block body
    (psum over a size-1 axis is identity; a 1-ring is dense attention), so
    the pipeline uses ONE body for every composition."""
    c = config
    cast = lambda p: p.astype(c.dtype)
    scale = 1.0 / c.head_dim ** 0.5
    tensor_axis = rules.heads if isinstance(rules.heads, str) else None
    seq_axis = rules.seq_act if isinstance(rules.seq_act, str) else None
    tp = mesh.shape[tensor_axis] if tensor_axis in mesh.shape else 1
    sp = mesh.shape[seq_axis] if seq_axis in mesh.shape else 1

    from ray_tpu.parallel.ring_attention import _ring_attention_local

    def attention(q, k, v):
        if sp > 1:
            return _ring_attention_local(
                q, k, v, axis_name=seq_axis, axis_size=sp, causal=True,
                scale=scale)
        return _dense_attention(q, k, v, scale=scale)

    def block(h, bp):
        bp = jax.tree.map(cast, bp)
        x = layer_norm(h, bp["ln1_g"], bp["ln1_b"])
        q = jnp.einsum("bld,dhk->blhk", x, bp["wq"],
                       preferred_element_type=jnp.float32).astype(c.dtype) + bp["bq"]
        kk = jnp.einsum("bld,dhk->blhk", x, bp["wk"],
                        preferred_element_type=jnp.float32).astype(c.dtype) + bp["bk"]
        vv = jnp.einsum("bld,dhk->blhk", x, bp["wv"],
                        preferred_element_type=jnp.float32).astype(c.dtype) + bp["bv"]
        if c.pos == "rope":
            off = (lax.axis_index(seq_axis) * h.shape[1]
                   if sp > 1 else 0)
            positions = off + jnp.arange(h.shape[1])
            q = rope(q, positions)
            kk = rope(kk, positions)
        o = attention(q, kk, vv)
        o = jnp.einsum("blhk,hkd->bld", o, bp["wo"],
                       preferred_element_type=jnp.float32)
        if tp > 1:
            o = lax.psum(o, tensor_axis)  # row-parallel reduce
        h = h + o.astype(c.dtype) + bp["bo"]

        x = layer_norm(h, bp["ln2_g"], bp["ln2_b"])
        u = linear(x, bp["w_up"], bp["b_up"])  # column-parallel: local slice
        u = gelu(u)
        d = jnp.einsum("blf,fd->bld", u, bp["w_down"],
                       preferred_element_type=jnp.float32)
        if tp > 1:
            d = lax.psum(d, tensor_axis)  # row-parallel reduce
        # Bias in f32 then cast — same order as ops.layers.linear.
        h = h + (d + bp["b_down"].astype(jnp.float32)).astype(c.dtype)
        return h

    return block


def forward(
    params: Dict,
    tokens: jax.Array,
    config: TransformerConfig,
    *,
    mesh: Optional[Mesh] = None,
    rules: Optional[ShardingRules] = None,
) -> jax.Array:
    """tokens [B, L] int32 → logits [B, L, padded_vocab] (compute dtype).

    When ``mesh``+``rules`` are provided, activations carry sharding
    constraints so XLA places the megatron collectives exactly where the
    recipe wants them (after attention out-proj / mlp down-proj).
    """
    c = config
    cast = lambda p: p.astype(c.dtype)

    def cstr(x, logical):
        if mesh is not None and rules is not None:
            return constrain(x, mesh, rules, logical)
        return x

    B, L = tokens.shape
    # Embedding lookup with an EXPLICIT table all-gather first: a gather
    # into a vocab(tensor)-sharded table forces the SPMD partitioner into
    # involuntary full rematerialization (replicate + repartition) inside
    # the op; constraining the table to (None, None) turns that into one
    # clean all-gather, and the activation constraint below re-shards the
    # result. (Megatron's masked-lookup+psum is the large-vocab
    # alternative; for GPT-2-class vocabs the gathered table is ~40MB bf16.)
    tbl = cstr(cast(params["tok_embed"]), (None, None))
    h = jnp.take(tbl, tokens, axis=0)
    positions = jnp.arange(L)
    if c.pos == "learned":
        h = h + cast(params["pos_embed"])[positions]
    h = cstr(h, ("batch", "seq_act", None))

    block_body = make_block_fn(c, mesh, rules)

    def block(h, bp):
        return block_body(h, bp), None

    if c.remat:
        if c.remat_policy == "dots":
            policy = jax.checkpoint_policies.dots_with_no_batch_dims_saveable
            block_fn = jax.checkpoint(block, policy=policy)
        else:
            block_fn = jax.checkpoint(block)
    else:
        block_fn = block
    h, _ = lax.scan(block_fn, h, params["blocks"])

    h = layer_norm(h, cast(params["lnf_g"]), cast(params["lnf_b"]))
    w_out = params["tok_embed"].T if c.tie_embeddings else params["lm_head"]
    logits = jnp.einsum("bld,dv->blv", h, cast(w_out), preferred_element_type=jnp.float32)
    logits = cstr(logits.astype(c.dtype), ("batch", "seq_act", "vocab"))
    return logits


def lm_loss(
    params: Dict,
    batch: Dict[str, jax.Array],
    config: TransformerConfig,
    *,
    mesh: Optional[Mesh] = None,
    rules: Optional[ShardingRules] = None,
):
    """Next-token LM loss. batch: {"tokens": [B, L]} (optionally "loss_mask").

    Positions beyond ``config.vocab_size`` (the pad region) never receive
    probability mass pressure from real labels; the pad logits train to -inf
    naturally.
    """
    tokens = batch["tokens"]
    logits = forward(params, tokens, config, mesh=mesh, rules=rules)
    labels = jnp.where(
        batch.get("loss_mask", jnp.ones_like(tokens))[:, 1:] > 0,
        tokens[:, 1:],
        -100,
    )
    loss, n = softmax_cross_entropy(logits[:, :-1], labels)
    return loss


def pp_lm_loss(
    params: Dict,
    batch: Dict[str, jax.Array],
    config: TransformerConfig,
    *,
    mesh: Mesh,
    rules: ShardingRules,
    num_microbatches: int,
):
    """``lm_loss`` with the block stack run as a GPipe pipeline over the
    ``pipe`` mesh axis (parallel.pipeline) — the capability the reference
    only gets by delegating to DeepSpeed (SURVEY §2.4), here differentiable
    end-to-end inside ONE jitted step. Embedding and LM head run replicated
    across pipe (identical inputs ⇒ identical math on every stage group);
    only the blocks hand activations stage-to-stage. Losses match the
    non-PP ``lm_loss`` exactly (same block body, same reduction)."""
    from jax.sharding import PartitionSpec as P

    from ray_tpu.parallel.pipeline import make_pipeline
    from ray_tpu.parallel.sharding import pytree_shardings

    c = config
    cast = lambda p: p.astype(c.dtype)
    tokens = batch["tokens"]
    B, L = tokens.shape
    assert B % num_microbatches == 0, (B, num_microbatches)
    dp = _mesh_axes_size(mesh, rules.batch)
    assert B % dp == 0 and (B // dp) % num_microbatches == 0, (
        f"per-device batch {B}/{dp} must split evenly into "
        f"{num_microbatches} microbatches")

    # Explicit table all-gather before the lookup (see forward()): avoids
    # the partitioner's involuntary-remat fallback on sharded-table gather.
    tbl = constrain(cast(params["tok_embed"]), mesh, rules, (None, None))
    h = jnp.take(tbl, tokens, axis=0)
    if c.pos == "learned":
        h = h + cast(params["pos_embed"])[jnp.arange(L)]
    h = constrain(h, mesh, rules, ("batch", "seq_act", None))

    # The per-device block composes TP (psum on tensor) and SP (ring
    # attention on seq) inside the pipeline's shard_map; weights enter
    # tensor-sharded per their logical axes (embed replicated — the
    # fsdp gather happens once at the shard_map boundary).
    block = make_tp_block_fn(c, mesh, rules)
    pp_rules = rules.update(embed=None)
    param_specs = jax.tree.map(
        pp_rules.mesh_axes,
        logical_axes(c)["blocks"],
        is_leaf=lambda x: isinstance(x, tuple) and all(
            isinstance(e, (str, type(None))) for e in x),
    )
    batch_axes = rules.batch
    seq_ax = rules.seq_act if isinstance(rules.seq_act, str) else None
    x_spec = P(batch_axes, None, seq_ax, None)
    pipeline = make_pipeline(
        lambda bp, x: block(x, bp),
        mesh,
        num_microbatches=num_microbatches,
        pipe_axis=rules.layers,
        batch_axes=batch_axes,
        x_spec=x_spec,
        param_specs=param_specs,
        remat=c.remat,
    )
    mb = B // num_microbatches
    # Microbatch index on the TRAILING side of the split: a batch-sharded
    # [B, ...] reshapes into [mb, M, ...] with zero data movement (each
    # device's contiguous rows stay its own); the [M, mb, ...] layout
    # would force an involuntary-remat repartition (pipeline docstring).
    x4 = h.reshape(mb, num_microbatches, L, h.shape[-1])
    x4 = jax.lax.with_sharding_constraint(
        x4, jax.sharding.NamedSharding(mesh, x_spec))
    h = pipeline(params["blocks"], x4)
    h = h.reshape(B, L, h.shape[-1])
    h = constrain(h, mesh, rules, ("batch", "seq_act", None))

    h = layer_norm(h, cast(params["lnf_g"]), cast(params["lnf_b"]))
    w_out = params["tok_embed"].T if c.tie_embeddings else params["lm_head"]
    logits = jnp.einsum("bld,dv->blv", h, cast(w_out),
                        preferred_element_type=jnp.float32).astype(c.dtype)
    logits = constrain(logits, mesh, rules, ("batch", "seq_act", "vocab"))
    labels = jnp.where(
        batch.get("loss_mask", jnp.ones_like(tokens))[:, 1:] > 0,
        tokens[:, 1:],
        -100,
    )
    loss, _n = softmax_cross_entropy(logits[:, :-1], labels)
    return loss

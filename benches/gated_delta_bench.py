"""The gated delta rule's two serve forms on the real chip, at the published
sizes of ``olmo-hybrid-7b`` (30 heads, key 96, value 192; 48 slots, 12 layers).

What CPU tests cannot say: (1) the chunk-wise prefill scan compiled for the
TPU against the token-by-token recurrence at 2,048 tokens; (2) the compiled
decode kernel ``gdn_decode`` against its plain form: the interpreted kernel
is exact on the CPU whatever XLA does around it, and the first compiled form
was not (XLA dropped the float32 -> bfloat16 -> float32 round trips that
split q, k and the gates into bfloat16 parts, leaving them 8 bits: state off
by 0.025 of 10; ``ops/layers.py:split3``); (3) the kernel's time a
layer. Prints one JSON line. Run it through the chip tool from the repo's
root: ``python3 benches/gated_delta_bench.py``."""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from ray_tpu.ops import gated_delta as gd  # noqa: E402

out = {"device": str(jax.devices()[0])}
T, H, DK, DV = 2048, 30, 96, 192
ks = jax.random.split(jax.random.key(5), 6)
unit = lambda a: a / jnp.linalg.norm(a, axis=-1, keepdims=True)
q = unit(jax.random.normal(ks[0], (T, H, DK))) * DK ** -0.5
k = unit(jax.random.normal(ks[1], (T, H, DK)))
v = jax.random.normal(ks[2], (T, H, DV)) * 3
A = jax.random.uniform(ks[3], (1, H)) * 16
g = -A * jnp.exp(jax.random.uniform(ks[4], (T, H), minval=np.log(1e-3), maxval=np.log(1e-1)))
beta = 2 * jax.nn.sigmoid(jax.random.normal(ks[5], (T, H)))
rec = jax.jit(gd.recurrence); chk = jax.jit(gd.chunked)
o_r, s_r = rec(q, k, v, g, beta); o_c, s_c = chk(q, k, v, g, beta)
jax.block_until_ready((o_r, o_c))
out["chunked_vs_recurrence"] = {"o_max_abs": float(jnp.abs(o_r - o_c).max()), "s_max_abs": float(jnp.abs(s_r - s_c).max()),
                                "o_scale": float(jnp.abs(o_r).max()), "s_scale": float(jnp.abs(s_r).max())}
t = time.perf_counter(); [jax.block_until_ready(chk(q, k, v, g, beta)) for _ in range(5)]
out["chunked_2048_ms"] = (time.perf_counter() - t) / 5 * 1e3
S, L = 48, 12
state = jax.random.normal(jax.random.key(9), (L, S, DK, H * DV), jnp.float32)
active = jnp.arange(S) % 7 != 3
args = (q[:S], k[:S], v[:S], jnp.exp(g[:S]), beta[:S], active, 5)
ker = jax.jit(lambda st, *a: gd.gdn_decode(st, *a))
ref = jax.jit(lambda st, *a: gd.gdn_decode_reference(st, *a))
s_k, o_k = ker(state, *args); s_f, o_f = ref(state, *args)
act = np.asarray(active)
out["kernel_vs_plain"] = {"s_max_abs": float(jnp.abs(s_k - s_f).max()), "o_max_abs": float(np.abs(np.asarray(o_k) - np.asarray(o_f))[act].max()),
                          "parked_bit_identical": bool((np.asarray(s_k)[5][~act] == np.asarray(state)[5][~act]).all()),
                          "other_layers_bit_identical": bool((np.asarray(s_k)[:5] == np.asarray(state)[:5]).all())}
don = jax.jit(lambda st, *a: gd.gdn_decode(st, *a), donate_argnums=(0,))
st = state
st, _ = don(st, *args); jax.block_until_ready(st)
t = time.perf_counter()
for _ in range(50):
    st, o = don(st, *args)
jax.block_until_ready(st)
ms = (time.perf_counter() - t) / 50 * 1e3
out["kernel_ms_a_layer_48_slots"] = ms
out["kernel_gb_per_s_all_slots"] = 2 * S * DK * H * DV * 4 / (ms / 1e3) / 1e9
print(json.dumps(out))

"""Operands and comparisons the paged-attention kernel's test files share
(``test_paged_attention_kernel.py`` and its ``_append`` / ``_latent`` /
``_window`` siblings): not collected, imported.
"""

import jax.numpy as jnp
import numpy as np

from ray_tpu.ops.paged_attention import _blocks_per_group

BT = 8   # block_tokens
NB = 6   # blocks per sequence (table width)
# 8 x 16 folds to 128 lanes: the kernel's walk (a loop over groups of table
# entries, each block one DMA). A width off the 128-lane grid takes the same
# groups through BlockSpecs; the odd widths below run that form.
H, D = 8, 16
GROUP = _blocks_per_group(BT, H * D, 4)    # table entries a loop iteration


def _setup(lengths, t_tokens, *, seed=0, pool_blocks=24, layers=1, layer=0,
           heads=H, dim=D, nb=NB, dead=0):
    """Random pool + one live block chain per slot; returns the operands of
    ``paged_attention`` / ``paged_attention_reference``: the pool is the
    whole model's, ``[layers, pool_blocks, BT, heads * dim]`` with distinct
    content in every layer, and ``layer`` picks the one attended. A length
    of None is a parked slot: length 0, an all-trash table. ``dead`` is the
    block id the entries past a live slot's chain hold (0: the trash block)."""
    rng = np.random.default_rng(seed)
    S = len(lengths)
    q = rng.standard_normal((S, t_tokens, heads, dim)).astype(np.float32)
    pool = (layers, pool_blocks, BT, heads * dim)
    k_pool = rng.standard_normal(pool).astype(np.float32)
    v_pool = rng.standard_normal(pool).astype(np.float32)
    tables = np.zeros((S, nb), np.int32)
    nxt = 1  # block 0 stays trash
    for s, ln in enumerate(lengths):
        if ln is None:
            continue
        live = min(-(-max(ln + t_tokens, 1) // BT), nb)
        tables[s, live:] = dead
        # A shuffled chain: the walk must dereference the table, not count.
        tables[s, :live] = rng.permutation(np.arange(nxt, nxt + live))
        nxt += live
    assert nxt <= pool_blocks - (dead > 0), (nxt, pool_blocks)
    lengths = [ln or 0 for ln in lengths]
    return (jnp.asarray(q), jnp.asarray(k_pool), jnp.asarray(v_pool),
            jnp.asarray(tables), jnp.asarray(np.asarray(lengths, np.int32)),
            layer)


def _assert_close(a, b, tol=2e-5):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=tol,
                               rtol=tol)


def _assert_live_close(out, ref, lengths, *, parked_is_zero=True):
    """Live slots (a length, not None) against the oracle; a parked slot's
    rows are zeros where the walk skips it (the oracle attends the trash
    block's first row there, and the form off the lane grid still does)."""
    live = np.asarray([ln is not None for ln in lengths])
    _assert_close(np.asarray(out)[live], np.asarray(ref)[live])
    if parked_is_zero:
        assert not np.asarray(out)[~live].any()

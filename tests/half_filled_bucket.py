"""A prompt that fills its bucket to under a half, as the families' test
files prefill it (not collected, imported): every family's prefill program
hands its attention kernels the count of real rows, and past the first query
tile a bucket's pad rows are tiles that the walk skips. The callers hold the
last real row's logits to their own reference's.
"""

import numpy as np

from ray_tpu.models.generate import PagedGenerator


def last_row(params, cfg, prompt, bucket: int, *, block_tokens: int = 16,
             kernel: str = "interpret"):
    """The logits after ``prompt`` prefilled from position 0 into slot 0
    through ``bucket``, the table as the engine writes it: the prompt's own
    blocks, every entry behind them the trash block."""
    bt = block_tokens
    gen = PagedGenerator(params, cfg, slots=1, num_blocks=bucket // bt + 2,
                         block_tokens=bt, max_len=bucket,
                         attention_kernel=kernel)
    table = np.zeros(gen.blocks_per_seq, np.int32)
    live = -(-len(prompt) // bt)
    table[:live] = 1 + np.arange(live)
    padded = np.zeros((1, bucket), np.int32)
    padded[0, :len(prompt)] = prompt
    out = gen.prefill_fn(bucket)(gen.params, *gen.init_state(), table, padded,
                                 0, len(prompt), 0, 0)
    return np.asarray(out[2][0])

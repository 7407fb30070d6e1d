"""Readers of the train driver's host clock."""

from __future__ import annotations

from benchmark.reduce import shapes
from benchmark.reduce.stats import median


def train_tok_s_chip(run, spec):
    """All the window's steps over all the window's time, per chip."""
    return (len(run["step_s"]) * run["tokens_per_step"]
            / (run["t_close"] - run["t_open"]) / run["chips"])


def train_step_ms(run, spec):
    return median(run["step_s"]) * 1e3


def mfu_train(run, spec):
    """(6N + 12 L T d) FLOP/token x tokens/s/chip over the chip's bf16 peak;
    N from the published sizes, recomputation not counted."""
    c = run["config"]
    n = shapes.gpt2_param_count(c["n_layer"], c["n_embd"], c["n_inner"],
                                c["vocab_size"], c["n_positions"])
    per_tok = shapes.train_flops_per_token(
        n, c["n_layer"], run["sequence_tokens"], c["n_embd"])
    return (100.0 * per_tok * train_tok_s_chip(run, spec)
            / run["peaks"]["bf16_flops_per_s"])

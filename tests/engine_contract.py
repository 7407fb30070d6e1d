"""What ``LLMEngine`` owes a request whatever family it serves.

Seven checks, each a function of ``(params, config, engine_kwargs)``. A
family's test file runs them as ONE parametrised test over its own tiny
model (``each_check``, with ``ENGINE_KW`` unless its model needs other
sizes) and holds the ``(prompt, tokens)`` pairs a check returns
(``length_cap``'s and ``one_fetch_order``'s streams: the others compare an
engine with an engine) to that family's reference. Not collected: no test
lives here.

Engines: the checks that change neither weights nor pool size share one
engine a model (``_shared``, kept for the worker's life: ``--dist loadfile``
gives a family's file to one worker); ``requeue`` builds its small pool,
``set_params`` the engine whose weights it swaps, ``cancel`` the fresh engine
it compares with. None is warmed: a check compiles the programs it calls.
"""

import jax
import numpy as np
import pytest

from ray_tpu.serve.llm import LLMEngine

# Sizes every tiny model takes: two blocks of 16 a sequence (a short context
# keeps the references' token-by-token passes to seconds). The XLA gather
# path: the kernels have their own tests, and interpreted they take five times
# as long to compile.
ENGINE_KW = dict(max_len=32, prompt_buckets=(16, 32), chunk=4, slots=2,
                 max_queue=0, block_tokens=16, pool_blocks=17,
                 attention_kernel="gather")

_SHARED = {}        # id(params) -> (params, engine)


def _engine(params, config, kw, name, **over):
    return LLMEngine(params, config, name=f"contract-{name}",
                     **{**kw, **over})


def _shared(params, config, kw):
    if id(params) not in _SHARED:
        # The tree is kept with its engine: an id is reused once its object
        # is collected.
        _SHARED[id(params)] = (params, _engine(params, config, kw, "shared"))
    return _SHARED[id(params)][1]


def _prompt(config, n, seed):
    rng = np.random.default_rng(seed)
    return [int(t) for t in rng.integers(1, config.vocab_size, n)]


def _drive(eng, reqs, limit=400):
    """This thread is the driver: step by hand until every request is done.
    Returns each request's tokens and the number of steps taken."""
    steps = 0
    while not all(r.done for r in reqs):
        assert steps < limit, "the engine made no progress"
        eng._step()
        steps += 1
    return [list(eng.drive(r)) for r in reqs], steps


def _drained(eng):
    assert eng._pending is None
    assert eng.kv.active_blocks() == 0
    assert not eng._slot_table.any() and not any(eng._slot_blocks)


def length_cap(params, config, kw):
    """(a) A request that asks past ``max_len`` ends ``length_cap`` with at
    most ``max_len - prompt`` tokens, and the pool drains."""
    eng = _shared(params, config, kw)
    prompt = _prompt(config, 5, 1)
    outcome = {}
    toks = list(eng.stream(prompt, max_new_tokens=10 * eng.max_len,
                           result=outcome))
    assert outcome["finish_reason"] == "length_cap"
    # Whole chunks up to the last that fits: no partial chunk is dispatched.
    room = eng.max_len - len(prompt)
    assert len(toks) == room - room % eng.chunk > 0
    _drained(eng)
    return [(prompt, toks)]


def cancel(params, config, kw):
    """(b) A request cancelled with a chunk in flight frees its slot and
    its blocks at once; the NEXT request in that slot gets the tokens a
    fresh engine gives it (a family with a slot state: written from zero,
    ``state_resets_total`` rose)."""
    eng = _shared(params, config, kw)
    victim = eng.submit(_prompt(config, 13, 2), max_new_tokens=24)
    eng._step()
    eng._step()         # two chunks dispatched, the first delivered
    slot = victim.slot
    assert slot is not None and victim.emitted == eng.chunk
    assert any(req is victim for _, req, _ in eng._pending.rows)
    eng._cancel(victim)
    assert victim.done and victim.finish_reason == "cancelled"
    assert eng._slot_req[slot] is None and eng.kv.active_blocks() == 0
    before = eng.stats()
    prompt = _prompt(config, 9, 3)
    nxt = eng.submit(prompt, max_new_tokens=12)
    # The first step of the next request fetches the victim's chunk, whose
    # tokens are dropped: nobody is told of them.
    eng._step()
    assert nxt.slot == slot and victim.emitted == eng.chunk
    (toks,), _ = _drive(eng, [nxt])
    after = eng.stats()
    if eng._slot_state:
        assert (after["state_resets_total"]
                - before["state_resets_total"]) == 1
    _drained(eng)
    fresh = _engine(params, config, kw, "fresh")
    assert toks == fresh.generate(prompt, max_new_tokens=12)
    return []


def requeue(params, config, kw):
    """(c) A pool too small for two prompts requeues the second
    (``admit_stopped_no_blocks_total``), and both finish with the tokens of
    their solo runs."""
    chunk, bt = kw["chunk"], kw["block_tokens"]
    jobs = [(_prompt(config, 14, 4), 3 * chunk), (_prompt(config, 15, 5),
                                                  2 * chunk)]
    need = max(-(-(len(p) + n) // bt) for p, n in jobs)
    eng = _engine(params, config, kw, "small-pool", pool_blocks=need + 1)
    reqs = [eng.submit(p, max_new_tokens=n) for p, n in jobs]
    outs, _ = _drive(eng, reqs)
    st = eng.stats()
    assert st["admit_stopped_no_blocks_total"] > 0
    assert st["admit_blocked_pool_s"] > 0
    assert all(r.finish_reason == "stop" for r in reqs)
    _drained(eng)
    solo = _shared(params, config, kw)
    for (p, n), out in zip(jobs, outs):
        assert out == solo.generate(p, max_new_tokens=n)
    return []


def sampled(params, config, kw):
    """(d) A sampled request with a fixed seed returns the same tokens
    alone (slot 0) and beside a greedy neighbour (slot 1)."""
    eng = _shared(params, config, kw)
    prompt, beside = _prompt(config, 7, 6), _prompt(config, 13, 7)
    how = dict(max_new_tokens=12, temperature=0.8, seed=123)
    alone = eng.generate(prompt, **how)
    assert alone != eng.generate(prompt, **{**how, "seed": 124})
    greedy = eng.submit(beside, max_new_tokens=16)
    drawn = eng.submit(prompt, **how)
    (g, d), _ = _drive(eng, [greedy, drawn])
    assert d == alone
    assert g == eng.generate(beside, max_new_tokens=16)
    _drained(eng)
    return []


def set_params(params, config, kw):
    """(e) ``set_params`` with a second seeded tree serves that tree's
    tokens, from an empty pool, and leaves no block pinned."""
    keys = iter(jax.random.split(jax.random.key(8),
                                 len(jax.tree.leaves(params))))
    other = jax.tree.map(
        lambda p: (p * (1 + 0.5 * jax.random.normal(next(keys), p.shape))
                   ).astype(p.dtype), params)
    prompt = _prompt(config, 12, 9)
    # Drawn, to tell the trees apart: a tiny random model's greedy stream
    # is much the same whatever its weights.
    draw = dict(max_new_tokens=12, temperature=1.0, seed=3)
    eng = _engine(other, config, kw, "swap")
    theirs = eng.generate(prompt, **draw)
    eng.set_params(params)
    assert eng.kv.stats()["kv_blocks_cached"] == 0
    solo = _shared(params, config, kw)
    ours = eng.generate(prompt, max_new_tokens=12)
    assert ours == solo.generate(prompt, max_new_tokens=12)
    assert eng.generate(prompt, **draw) == solo.generate(
        prompt, **draw) != theirs
    eng.set_params(other)
    # The chain ``params`` left behind went with the pool: no hit splices
    # its rows into the other tree's stream.
    assert eng.generate(prompt, **draw) == theirs
    assert eng.kv.stats()["kv_hit_tokens"] == 0
    _drained(eng)
    return []


def one_fetch_order(params, config, kw):
    """(f) On one long request every decode dispatch after the first went
    out with the chunk before it unfetched, and one draining step delivered
    the last: the one fetch order, with whatever the family threads from
    program to program."""
    eng = _shared(params, config, kw)
    prompt = _prompt(config, 11, 10)
    n = 4 * eng.chunk + 1
    before = eng.stats()
    (toks,), steps = _drive(eng, [eng.submit(prompt, max_new_tokens=n)])
    after = eng.stats()
    dispatched = after["steps_total"] - before["steps_total"]
    assert dispatched == -(-n // eng.chunk) == steps - 1
    assert (after["steps_ahead_total"]
            - before["steps_ahead_total"]) == dispatched - 1
    assert len(toks) == n
    _drained(eng)
    return [(prompt, toks)]


def dispatch_failure(params, config, kw):
    """(g) A decode dispatch that raises fails every request the engine
    holds (in a slot, waiting, its last chunk unfetched) and nobody else;
    the engine starts over empty, slot state and pool, and serves the next
    request the tokens it served before."""
    eng = _shared(params, config, kw)
    prompt = _prompt(config, 10, 11)
    before = eng.generate(prompt, max_new_tokens=12)
    reqs = [eng.submit(_prompt(config, 8, 12 + i), max_new_tokens=8 * (i + 1))
            for i in range(3)]      # two slots: the third waits
    eng._step()
    eng._step()
    # The step that fails retires the first by count, its last chunk
    # unfetched, and gives the third its slot before it dispatches.
    assert reqs[0].scheduled == reqs[0].max_new and not reqs[0].done
    assert reqs[2].slot is None

    def lost(*operands):
        raise RuntimeError("device lost")

    eng._run_decode = lost
    try:
        with pytest.raises(RuntimeError, match="device lost"):
            eng._step()
    finally:
        del eng._run_decode
    assert reqs[0].retiring == "stop"
    for req in reqs:
        assert req.done and req.finish_reason == "error"
        with pytest.raises(RuntimeError, match="device lost"):
            list(eng.drive(req))
    _drained(eng)
    assert eng.generate(prompt, max_new_tokens=12) == before
    _drained(eng)
    return []


each_check = pytest.mark.parametrize(
    "check",
    (length_cap, cancel, requeue, sampled, set_params, one_fetch_order,
     dispatch_failure),
    ids=lambda check: check.__name__)

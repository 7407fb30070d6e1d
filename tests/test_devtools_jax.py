"""jaxlint + jitcheck: the four JAX-aware static checks trip on seeded
violations and stay quiet on their clean twins, the pragma/baseline
machinery covers them, and the runtime compile-churn guard counts
compilations per (site, signature) and enforces the steady-state
contract — including end-to-end on a warmed paged engine, whose
mixed-bucket burst must trigger ZERO new XLA compilations and zero
implicit device→host reads.
"""

import textwrap
import threading

import jax
import numpy as np
import pytest

from ray_tpu.devtools import jaxlint, jitcheck, lint

# jitcheck counts backend compiles and their seconds: here a compile is a
# compile, not a program another file left in the run's compile cache.
pytestmark = pytest.mark.usefixtures("no_compile_cache")


def _write(tmp_path, rel, src):
    p = tmp_path / rel
    p.parent.mkdir(parents=True, exist_ok=True)
    p.write_text(textwrap.dedent(src))
    return p


def _jax_findings(tmp_path, check=None):
    found = [f for f in lint.lint_tree(str(tmp_path))
             if f.check in jaxlint.JAX_CHECKS]
    if check is not None:
        found = [f for f in found if f.check == check]
    return found


# ---------------------------------------------------------------------------
# jit-churn
# ---------------------------------------------------------------------------


class TestJitChurn:
    def test_local_jit_flagged(self, tmp_path):
        _write(tmp_path, "mod.py", """
            import jax

            class Model:
                def evaluate(self, xs):
                    fwd = jax.jit(self.forward)   # rebuilt per evaluate()
                    return [fwd(x) for x in xs]
            """)
        found = _jax_findings(tmp_path, "jit-churn")
        assert len(found) == 1 and "fwd" in found[0].message
        assert found[0].scope == "Model.evaluate"

    def test_immediate_call_flagged(self, tmp_path):
        _write(tmp_path, "mod.py", """
            import jax

            def step(f, x):
                return jax.jit(f)(x)   # compile-and-discard every call
            """)
        assert len(_jax_findings(tmp_path, "jit-churn")) == 1

    def test_partial_form_flagged(self, tmp_path):
        _write(tmp_path, "mod.py", """
            import functools
            import jax

            def run(f, x):
                g = functools.partial(jax.jit, donate_argnums=(0,))(f)
                return g(x)
            """)
        assert len(_jax_findings(tmp_path, "jit-churn")) == 1

    def test_cached_builder_and_module_scope_clean(self, tmp_path):
        _write(tmp_path, "mod.py", """
            import jax

            top = jax.jit(lambda x: x)      # module scope: compiled once

            class Model:
                def __init__(self):
                    self._fwd = jax.jit(self.forward)   # cached on self

                def lazy(self):
                    if self._fwd is None:
                        self._fwd = jax.jit(self.forward)
                    return self._fwd

                def build(self):
                    return jax.jit(self.forward)  # one-shot builder

                def build2(self):
                    f = jax.jit(self.forward)     # escapes via return
                    return f

                def register(self, table):
                    f = jax.jit(self.forward)     # escapes into a call
                    table.add(f)

                def cache_slot(self, table, k):
                    f = jax.jit(self.forward)     # escapes via subscript
                    table[k] = f
            """)
        assert _jax_findings(tmp_path, "jit-churn") == []

    def test_static_argnums_data_derived(self, tmp_path):
        _write(tmp_path, "mod.py", """
            import functools
            import jax

            @functools.partial(jax.jit, static_argnums=(1,))
            def pad_to(x, n):
                return x[:n]

            @functools.partial(jax.jit, static_argnames=("width",))
            def pad_named(x, width=8):
                return x[:width]

            BUCKET = 128

            def hot(batch, x):
                pad_to(x, len(batch))          # one compile per batch size
                pad_named(x, width=x.shape[0])  # same, by name
                pad_to(x, BUCKET)              # constant: fine
                pad_named(x, width=BUCKET)     # constant: fine
            """)
        found = _jax_findings(tmp_path, "jit-churn")
        assert len(found) == 2
        assert {f.line for f in found} == {16, 17}


# ---------------------------------------------------------------------------
# host-sync
# ---------------------------------------------------------------------------

_HOT_HEADER = """
    import jax
    import numpy as np

    class Engine:
        def _run_decode(self, active):
            return self._decode_fn(self.params, active)

        def _deliver(self, st, due, queued):
            return jax.device_get(due.take())

"""


class TestHostSync:
    def test_sinks_flagged_in_hot_scope(self, tmp_path):
        _write(tmp_path, "serve/llm.py", _HOT_HEADER + """
        def _step_inner(self):
            toks = self._run_decode(self._active)
            host = np.asarray(toks)         # implicit sync
            first = float(toks[0])          # coercion sync
            n = toks.sum().item()           # .item() sync
            if toks.any():                  # truthiness sync
                pass
            return host, first, n
        """)
        found = _jax_findings(tmp_path, "host-sync")
        kinds = {f.detail.split(":")[0] for f in found}
        assert kinds == {"np-sync", "coerce", "item", "truthiness"}

    def test_device_get_twin_clean(self, tmp_path):
        _write(tmp_path, "serve/llm.py", _HOT_HEADER + """
        def _step_inner(self):
            toks = self._run_decode(self._active)
            host = jax.device_get(toks)     # the sanctioned batched fetch
            first = float(host[0])
            n = host.sum().item()
            if host.any():
                pass
            return host, first, n
        """)
        assert _jax_findings(tmp_path, "host-sync") == []

    def test_cold_files_not_patrolled(self, tmp_path):
        _write(tmp_path, "util/cold.py", """
            import jax.numpy as jnp
            import numpy as np

            def checkpoint(params):
                return np.asarray(jnp.stack(params))  # cold path: fine
            """)
        assert _jax_findings(tmp_path, "host-sync") == []

    def test_coverage_guard_fires_on_missing_scope(self, tmp_path):
        _write(tmp_path, "serve/llm.py", """
            class Engine:
                def _step_inner(self):
                    return None

                def _deliver(self, st, due, queued):
                    return None
            """)
        found = _jax_findings(tmp_path, "host-sync")
        assert len(found) == 1
        assert "_run_decode" in found[0].message
        assert found[0].detail == "hot-scope-missing:_run_decode"

    def test_nested_generator_is_walked(self, tmp_path):
        _write(tmp_path, "models/generate.py", _HOT_HEADER + """
        def generate(self, prompt):
            last = self._prefill_fn(self.params, prompt)

            def run():
                nxt = last
                while True:
                    yield int(nxt[0])       # per-token sync in the closure
            return run()
        """)
        found = _jax_findings(tmp_path, "host-sync")
        assert any(f.detail == "coerce:int" for f in found)


# ---------------------------------------------------------------------------
# key-reuse
# ---------------------------------------------------------------------------


class TestKeyReuse:
    def test_reuse_flagged(self, tmp_path):
        _write(tmp_path, "mod.py", """
            import jax

            def sample(shape):
                key = jax.random.PRNGKey(0)
                a = jax.random.normal(key, shape)
                b = jax.random.uniform(key, shape)   # reuse!
                return a + b
            """)
        found = _jax_findings(tmp_path, "key-reuse")
        assert len(found) == 1 and "'key'" in found[0].message

    def test_loop_reuse_flagged(self, tmp_path):
        _write(tmp_path, "mod.py", """
            import jax

            def rollout(key, n):
                outs = []
                for _ in range(n):
                    outs.append(jax.random.normal(key, (4,)))  # every iter
                return outs
            """)
        assert len(_jax_findings(tmp_path, "key-reuse")) == 1

    def test_split_then_use_clean(self, tmp_path):
        _write(tmp_path, "mod.py", """
            import jax

            def sample(key, shape):
                key, sub = jax.random.split(key)
                a = jax.random.normal(sub, shape)
                key, sub = jax.random.split(key)
                b = jax.random.uniform(sub, shape)
                return a + b

            def loop(self, n):
                for _ in range(n):
                    self._key, sub = jax.random.split(self._key)
                    yield jax.random.normal(sub, (4,))
            """)
        assert _jax_findings(tmp_path, "key-reuse") == []

    def test_branches_fold_in_and_shadowing_clean(self, tmp_path):
        _write(tmp_path, "mod.py", """
            import jax

            def branchy(key, logits, discrete):
                if discrete:
                    return jax.random.categorical(key, logits)
                else:
                    return jax.random.normal(key, logits.shape)

            def folded(key, n):
                return [jax.random.normal(jax.random.fold_in(key, i), (2,))
                        for i in range(n)]

            def outer(key):
                k = iter(jax.random.split(key, 4))

                def nrm(key, shape):
                    # param shadows the outer key — fresh key per call
                    return jax.random.normal(key, shape)

                return nrm(next(k), (2,)), nrm(next(k), (3,))
            """)
        assert _jax_findings(tmp_path, "key-reuse") == []


# ---------------------------------------------------------------------------
# donate-uaf
# ---------------------------------------------------------------------------


class TestDonateUaf:
    def test_read_after_donate_flagged(self, tmp_path):
        _write(tmp_path, "mod.py", """
            import jax

            update = jax.jit(lambda p, g: p, donate_argnums=(0,))

            def train_step(params, grads):
                new = update(params, grads)
                stale = params["w"]          # donated buffer: dead!
                return new, stale
            """)
        found = _jax_findings(tmp_path, "donate-uaf")
        assert len(found) == 1 and "'params'" in found[0].message

    def test_rebind_through_clean(self, tmp_path):
        _write(tmp_path, "mod.py", """
            import functools
            import jax

            @functools.partial(jax.jit, donate_argnums=(0, 1))
            def set_last(last, keys, row):
                return last, keys

            def attach(last, keys, row):
                last, keys = set_last(last, keys, row)  # rebind-through
                return last.sum() + keys.sum()

            def swap(params, grads, update):
                params = update(params, grads)
                return params
            """)
        assert _jax_findings(tmp_path, "donate-uaf") == []


# ---------------------------------------------------------------------------
# pragmas + baseline round-trip
# ---------------------------------------------------------------------------


class TestSuppression:
    def test_pragma_suppresses_jax_checks(self, tmp_path):
        _write(tmp_path, "mod.py", """
            import jax

            def churn(f, x):
                # raylint: ignore[jit-churn]
                g = jax.jit(f)
                return g(x)
            """)
        assert _jax_findings(tmp_path) == []

    def test_baseline_round_trip(self, tmp_path):
        _write(tmp_path, "mod.py", """
            import jax

            def sample(key, shape):
                a = jax.random.normal(key, shape)
                return a + jax.random.uniform(key, shape)
            """)
        baseline = tmp_path / "baseline.txt"
        assert lint.main([str(tmp_path), "--baseline", str(baseline),
                          "-q"]) == 1
        assert lint.main([str(tmp_path), "--baseline", str(baseline),
                          "--update-baseline"]) == 0
        assert lint.main([str(tmp_path), "--baseline", str(baseline),
                          "-q"]) == 0
        # fingerprints are line-free: shifting the finding keeps it accepted
        src = (tmp_path / "mod.py").read_text()
        (tmp_path / "mod.py").write_text("# moved\n" + src)
        assert lint.main([str(tmp_path), "--baseline", str(baseline),
                          "-q"]) == 0

    def test_profile_reports_jax_phases(self, tmp_path):
        _write(tmp_path, "mod.py", "x = 1\n")
        linter = lint.Linter(str(tmp_path))
        linter.run()
        for phase in jaxlint.JAX_CHECKS:
            assert phase in linter.timings


# ---------------------------------------------------------------------------
# jitcheck (runtime)
# ---------------------------------------------------------------------------


@pytest.fixture
def jc():
    """jitcheck installed for the test; leaves a suite-level install
    (RAY_TPU_JIT_CHECK_ENABLED=1 runs) untouched."""
    was = jitcheck.installed()
    if not was:
        jitcheck.install()
    yield jitcheck
    if not was:
        jitcheck.uninstall()


class TestJitcheck:
    def test_compile_counting_per_site_and_signature(self, jc):
        f = jax.jit(lambda x: x * 3)
        n0 = jc.total_compiles()
        f(np.ones(3, np.float32))
        f(np.ones(3, np.float32))   # cached: no new compile
        assert jc.total_compiles() == n0 + 1
        f(np.ones(5, np.float32))   # new shape: one more
        assert jc.total_compiles() == n0 + 2
        sites = {site for site, _sig in jc.compile_counts()}
        assert any("test_devtools_jax.py" in s for s in sites)
        sigs = {sig for _s, sig in jc.compile_counts()
                if "test_devtools_jax.py" in _s}
        assert "(float32[3])" in sigs and "(float32[5])" in sigs
        secs = jc.compile_seconds_by_site()
        assert any("test_devtools_jax.py" in s and v > 0
                   for s, v in secs.items())

    def test_steady_state_allows_warm_calls_and_device_get(self, jc):
        f = jax.jit(lambda x: x + 1)
        f(np.ones(4, np.float32))   # warm
        v0 = len(jc.violations())
        with jc.steady_state():
            y = f(np.ones(4, np.float32))
            host = jax.device_get(y)
        assert host.sum() == 8.0
        assert len(jc.violations()) == v0

    @pytest.mark.jit_violations("provokes an implicit read on purpose")
    def test_implicit_read_recorded(self, jc):
        f = jax.jit(lambda x: x * 2)
        y = f(np.ones(2, np.float32))
        v0 = len(jc.violations())
        with jc.steady_state():
            float(y.sum())          # implicit device->host read
        new = jc.violations()[v0:]
        assert any("implicit device->host read" in v for v in new)

    @pytest.mark.jit_violations("provokes a steady-state compile on purpose")
    def test_shape_churn_fails_strict_guard(self, jc):
        f = jax.jit(lambda x: x - 1)
        f(np.ones(4, np.float32))   # warm one bucket only
        with pytest.raises(jitcheck.SteadyStateViolation):
            with jc.steady_state(strict=True):
                f(np.ones(7, np.float32))   # unwarmed shape: compiles

    def test_steady_state_noop_when_not_installed(self):
        if jitcheck.installed():
            pytest.skip("suite runs with jitcheck installed")
        with jitcheck.steady_state(strict=True):
            jax.jit(lambda x: x)(np.ones(2))  # fine: guard inert

    def test_uninstall_restores_jax(self):
        was = jitcheck.installed()
        if not was:
            jitcheck.install()
            jitcheck.uninstall()
            assert not jitcheck.installed()
        f = jax.jit(lambda x: x)
        assert f(np.ones(1, np.float32)).shape == (1,)


# ---------------------------------------------------------------------------
# e2e: the steady-state decode invariant
# ---------------------------------------------------------------------------


class TestEngineSteadyState:
    def test_warmed_paged_engine_burst_zero_compiles(self, jc):
        """After warmup, a mixed-bucket greedy+sampled burst (the whole
        request path: admission, prefill, batched decode, distribution)
        triggers ZERO new XLA compilations and zero implicit host reads —
        the invariant every serve perf number rests on."""
        from ray_tpu.models import transformer
        from ray_tpu.serve.llm import LLMEngine

        cfg = transformer.tiny(max_seq_len=64)
        params = transformer.init_params(cfg, jax.random.key(0))
        eng = LLMEngine(params, cfg, prompt_buckets=(16, 32), chunk=4,
                             slots=2, max_queue=4, name="jitcheck-e2e",
                             block_tokens=8, pool_blocks=65)
        eng.warmup()
        assert eng._steady
        warm_compiles = jc.total_compiles()
        assert warm_compiles > 0  # warmup really did compile the programs

        prompts = [[7, 3, 11], [2, 4, 6, 8, 10], [1] * 9,
                   list(range(100, 125))]  # last spans the 32 bucket
        v0 = len(jc.violations())
        outs = [None] * len(prompts)

        def run(i):
            temp = 0.0 if i % 2 == 0 else 0.8
            outs[i] = eng.generate(list(prompts[i]), max_new_tokens=6,
                                   temperature=temp, seed=i)

        threads = [threading.Thread(target=run, args=(i,))
                   for i in range(len(prompts))]
        for t in threads:
            t.start()
        for t in threads:
            t.join()

        assert all(o is not None and len(o) > 0 for o in outs)
        assert jc.total_compiles() == warm_compiles, (
            "steady-state burst compiled:",
            jc.compile_counts())
        assert jc.violations()[v0:] == []

"""repro.lint checker suite (DESIGN.md §17).

Each rule gets ≥2 positive fixtures (seeded violations the checker must
catch, with the right rule ID and line) and ≥1 negative fixture (the
idiomatic clean spelling that must NOT be flagged).  Plus: suppression
comments, the CLI exit/report contract, stable rule IDs, and the
acceptance gate that the repo's own tree lints clean.
"""
import json
import textwrap
from pathlib import Path

import pytest

from repro.lint import all_rules, lint_file, lint_paths
from repro.lint.cli import main as lint_main

REPO = Path(__file__).resolve().parents[1]


def _lint(tmp_path, source, name="mod.py"):
    p = tmp_path / name
    p.parent.mkdir(parents=True, exist_ok=True)
    p.write_text(textwrap.dedent(source))
    return lint_file(p)


def _ids(findings):
    return [f.rule.id for f in findings]


def _only(findings, rule_id):
    return [f for f in findings if f.rule.id == rule_id]


# =====================================================================
# RPL101 donated-reuse
# =====================================================================

def test_rpl101_read_after_scan_step_donation(tmp_path):
    found = _lint(tmp_path, """
        from repro.core.engine import make_scan_step

        def run(bundle, fn):
            step = make_scan_step(fn, bundle, chunk=8)
            data, rep = bundle.data, bundle.replicated
            data2, rep2, trace = step(data, rep, 0)
            return data.sum()
    """)
    hits = _only(found, "RPL101")
    assert len(hits) == 1
    assert hits[0].line == 8
    assert "'data'" in hits[0].message


def test_rpl101_carried_output_slot_reused(tmp_path):
    found = _lint(tmp_path, """
        from repro.core.engine import make_chunk_cost_step

        def run(bundle, light, cost, last):
            step = make_chunk_cost_step(light, cost, bundle, chunk=8)
            d, rep = bundle.data, bundle.replicated
            d, rep, new_last, trace = step(d, rep, 0, last)
            print(last)
    """)
    hits = _only(found, "RPL101")
    assert len(hits) == 1 and "'last'" in hits[0].message


def test_rpl101_loop_carried_donation(tmp_path):
    # donating in one loop trip and reading at the top of the next
    found = _lint(tmp_path, """
        from repro.core.engine import make_step

        def run(bundle, fn, data, rep):
            step = make_step(fn, bundle)
            for i in range(10):
                fresh, out = step(data, rep)
    """)
    assert _ids(found) == ["RPL101"]


def test_rpl101_negative_rebinding_and_donate_false(tmp_path):
    found = _lint(tmp_path, """
        from repro.core.engine import make_scan_step, make_step

        def clean(bundle, fn):
            step = make_scan_step(fn, bundle, chunk=8)
            data, rep = bundle.data, bundle.replicated
            for i in range(4):
                data, rep, trace = step(data, rep, i)
            return data

        def bench(bundle, fn, data, rep):
            step = make_step(fn, bundle, donate=False)
            for _ in range(3):
                out = step(data, rep)      # donate=False: reuse is fine
            return out
    """)
    assert _only(found, "RPL101") == []


# =====================================================================
# RPL201 blockspec-grid
# =====================================================================

_PALLAS_HEADER = """
    from jax.experimental import pallas as pl

    def kernel(x_ref, o_ref):
        o_ref[...] = x_ref[...]
"""


def test_rpl201_block_divisor_mismatch(tmp_path):
    found = _lint(tmp_path, _PALLAS_HEADER + """
        def fwd(x, n_full, block_n, block_m, interpret=False):
            return pl.pallas_call(
                kernel,
                grid=(n_full // block_n,),
                in_specs=[pl.BlockSpec((block_m, 4), lambda i: (i, 0))],
                out_specs=[pl.BlockSpec((block_m, 4), lambda i: (i, 0))],
                interpret=interpret,
            )(x)
    """)
    hits = _only(found, "RPL201")
    assert len(hits) == 2          # both specs use the wrong block name
    assert "block_n" in hits[0].message and "block_m" in hits[0].message


def test_rpl201_index_map_arity_mismatch(tmp_path):
    found = _lint(tmp_path, _PALLAS_HEADER + """
        def fwd(x, n_full, m_full, block_n, block_m, interpret=False):
            return pl.pallas_call(
                kernel,
                grid=(n_full // block_n, m_full // block_m),
                in_specs=[pl.BlockSpec((block_n, block_m),
                                       lambda i: (i, 0))],
                interpret=interpret,
            )(x)
    """)
    hits = _only(found, "RPL201")
    assert len(hits) == 1 and "2 axes" in hits[0].message


def test_rpl201_input_block_ignores_grid_index(tmp_path):
    found = _lint(tmp_path, _PALLAS_HEADER + """
        def fwd(x, n_full, block_n, interpret=False):
            return pl.pallas_call(
                kernel,
                grid=(n_full // block_n,),
                in_specs=[pl.BlockSpec((block_n, 4), lambda i: (0, 0))],
                interpret=interpret,
            )(x)
    """)
    hits = _only(found, "RPL201")
    assert len(hits) == 1 and "same input block" in hits[0].message


def test_rpl201_cdiv_grid(tmp_path):
    # a ragged-last-block grid steps in units of cdiv's divisor: the
    # consistent spec passes, the one blocked by another name is caught
    found = _lint(tmp_path, _PALLAS_HEADER + """
        def fwd(x, n, block_n, block_m, interpret=False):
            return pl.pallas_call(
                kernel,
                grid=(pl.cdiv(n, block_n),),
                in_specs=[pl.BlockSpec((block_n, 4), lambda i: (i, 0)),
                          pl.BlockSpec((block_m, 4), lambda i: (i, 0))],
                out_specs=pl.BlockSpec((block_n, 4), lambda i: (i, 0)),
                interpret=interpret,
            )(x, x)
    """)
    hits = _only(found, "RPL201")
    assert len(hits) == 1 and hits[0].line == 12
    assert "'block_n'" in hits[0].message and "'block_m'" in hits[0].message


def test_rpl201_negative_idiomatic_and_accumulator(tmp_path):
    # the repo idiom: matching divisor/block names, plus an accumulator
    # out_spec pinned to one block (legit on TPU's sequential grid)
    found = _lint(tmp_path, _PALLAS_HEADER + """
        def fwd(S, W, k_full, block_k, P, A, interpret=False):
            return pl.pallas_call(
                kernel,
                grid=(k_full // block_k,),
                in_specs=[
                    pl.BlockSpec((block_k, P), lambda i: (i, 0)),
                    pl.BlockSpec((block_k, A), lambda i: (i, 0)),
                ],
                out_specs=[pl.BlockSpec((P, A), lambda i: (0, 0))],
                interpret=interpret,
            )(S, W)
    """)
    assert _only(found, "RPL201") == []


# =====================================================================
# RPL202 missing-interpret
# =====================================================================

def test_rpl202_no_interpret_kwarg(tmp_path):
    found = _lint(tmp_path, _PALLAS_HEADER + """
        def fwd(x, n_full, block_n):
            return pl.pallas_call(
                kernel,
                grid=(n_full // block_n,),
                in_specs=[pl.BlockSpec((block_n, 4), lambda i: (i, 0))],
            )(x)
    """)
    hits = _only(found, "RPL202")
    assert len(hits) == 1 and "fallback" in hits[0].message


def test_rpl202_hardcoded_interpret_mode(tmp_path):
    found = _lint(tmp_path, _PALLAS_HEADER + """
        def fwd(x, n_full, block_n):
            return pl.pallas_call(
                kernel,
                grid=(n_full // block_n,),
                in_specs=[pl.BlockSpec((block_n, 4), lambda i: (i, 0))],
                interpret=True,
            )(x)
    """)
    hits = _only(found, "RPL202")
    assert len(hits) == 1 and "hardcodes" in hits[0].message


def test_rpl202_negative_plumbed_interpret(tmp_path):
    found = _lint(tmp_path, _PALLAS_HEADER + """
        from repro.kernels.common import auto_interpret

        def fwd(x, n_full, block_n, interpret=None):
            if interpret is None:
                interpret = auto_interpret()
            return pl.pallas_call(
                kernel,
                grid=(n_full // block_n,),
                in_specs=[pl.BlockSpec((block_n, 4), lambda i: (i, 0))],
                interpret=interpret,
            )(x)
    """)
    assert _only(found, "RPL202") == []


# =====================================================================
# RPL203 ref-parity (import-and-inspect)
# =====================================================================

def test_rpl203_signature_drift_and_missing_wrapper(tmp_path):
    fam = tmp_path / "kernels" / "fam"
    fam.mkdir(parents=True)
    (fam / "ref.py").write_text(textwrap.dedent("""
        def foo_ref(a, b, gamma):
            return a + b * gamma

        def bar_ref(a):
            return a
    """))
    found = _lint(tmp_path, """
        def foo(a, b, *, use_kernel=True, interpret=None, block_n=128):
            return a + b
    """, name="kernels/fam/ops.py")
    hits = _only(found, "RPL203")
    msgs = " | ".join(h.message for h in hits)
    assert len(hits) == 2
    assert "drifted" in msgs and "gamma" in msgs   # foo lost a param
    assert "bar" in msgs                           # bar_ref has no bar


def test_rpl203_missing_ref_sibling(tmp_path):
    found = _lint(tmp_path, """
        def foo(a, b):
            return a + b
    """, name="kernels/solo/ops.py")
    hits = _only(found, "RPL203")
    assert len(hits) == 1 and "no sibling ref.py" in hits[0].message


def test_rpl203_negative_parity_ok(tmp_path):
    fam = tmp_path / "kernels" / "good"
    fam.mkdir(parents=True)
    (fam / "ref.py").write_text(textwrap.dedent("""
        def foo_ref(a, b, gamma):
            return a + b * gamma
    """))
    found = _lint(tmp_path, """
        def foo(a, b, gamma, *, use_kernel=True, interpret=None,
                block_n=128):
            return a + b * gamma
    """, name="kernels/good/ops.py")
    assert _only(found, "RPL203") == []


# =====================================================================
# RPL301 traced-branch
# =====================================================================

def test_rpl301_if_on_traced_value(tmp_path):
    found = _lint(tmp_path, """
        import jax

        @jax.jit
        def f(x):
            if x > 0:
                return x
            return -x
    """)
    hits = _only(found, "RPL301")
    assert len(hits) == 1 and hits[0].line == 6


def test_rpl301_while_on_traced_reduction(tmp_path):
    found = _lint(tmp_path, """
        import jax
        import jax.numpy as jnp

        @jax.jit
        def g(x):
            while jnp.sum(x) > 1.0:
                x = x * 0.5
            return x
    """)
    assert len(_only(found, "RPL301")) == 1


def test_rpl301_scan_body_branch(tmp_path):
    # reachability through lax.scan, not just @jit
    found = _lint(tmp_path, """
        import jax
        import jax.numpy as jnp

        def run(xs):
            def body(carry, x):
                if carry > 0:
                    carry = carry + x
                return carry, carry
            return jax.lax.scan(body, jnp.float32(0), xs)
    """)
    assert len(_only(found, "RPL301")) == 1


def test_rpl301_negative_static_idioms(tmp_path):
    # the repo's idioms: `if axes:`, defaulted control params, metadata
    # attributes, shape-query helpers — none may be flagged
    found = _lint(tmp_path, """
        import jax
        import jax.numpy as jnp
        from functools import partial

        @partial(jax.jit, static_argnames=("mode",))
        def f(x, axes, mode, use_kernel=None):
            if axes:
                x = jax.lax.psum(x, axes)
            if use_kernel is None:
                use_kernel = True
            if mode == "sparse":
                x = x * 2
            if x.ndim == 2:
                x = x[None]
            n = len(x.shape)
            flat = [v for v in (x, x) if jnp.ndim(v) > 0]
            return jnp.where(x > 0, x, -x), n, flat
    """)
    assert _only(found, "RPL301") == []


# =====================================================================
# RPL302 host-cast
# =====================================================================

def test_rpl302_float_cast(tmp_path):
    found = _lint(tmp_path, """
        import jax
        import jax.numpy as jnp

        @jax.jit
        def f(x):
            return float(jnp.sum(x))
    """)
    hits = _only(found, "RPL302")
    assert len(hits) == 1 and "float()" in hits[0].message


def test_rpl302_item_call(tmp_path):
    found = _lint(tmp_path, """
        import jax

        @jax.jit
        def f(x):
            s = x.sum()
            return s.item()
    """)
    hits = _only(found, "RPL302")
    assert len(hits) == 1 and ".item()" in hits[0].message


def test_rpl302_negative_host_side_cast(tmp_path):
    # not jit-reachable -> host code may cast freely
    found = _lint(tmp_path, """
        import jax.numpy as jnp

        def summarize(x):
            return float(jnp.sum(x))
    """)
    assert _only(found, "RPL302") == []


# =====================================================================
# RPL303 numpy-on-traced
# =====================================================================

def test_rpl303_np_call_on_traced(tmp_path):
    found = _lint(tmp_path, """
        import jax
        import numpy as np

        @jax.jit
        def f(x):
            return np.sum(x)
    """)
    hits = _only(found, "RPL303")
    assert len(hits) == 1 and "np.sum" in hits[0].message


def test_rpl303_np_asarray_in_scan_body(tmp_path):
    found = _lint(tmp_path, """
        import jax
        import numpy as np

        def run(xs, c0):
            def body(c, x):
                return c + np.asarray(x), c
            return jax.lax.scan(body, c0, xs)
    """)
    assert len(_only(found, "RPL303")) == 1


def test_rpl303_negative_np_on_static_metadata(tmp_path):
    found = _lint(tmp_path, """
        import jax
        import numpy as np

        @jax.jit
        def f(x):
            n = np.prod(x.shape)     # static metadata: fine
            return x / n
    """)
    assert _only(found, "RPL303") == []


# =====================================================================
# RPL401 f64-dtype
# =====================================================================

def test_rpl401_jnp_float64_reference(tmp_path):
    found = _lint(tmp_path, """
        import jax.numpy as jnp

        def f(x):
            return x.astype(jnp.float64)
    """)
    assert len(_only(found, "RPL401")) == 1


def test_rpl401_dtype_string_in_jax_call(tmp_path):
    found = _lint(tmp_path, """
        import jax.numpy as jnp

        def f():
            return jnp.zeros((4, 4), dtype="float64")
    """)
    assert len(_only(found, "RPL401")) == 1


def test_rpl401_negative_host_numpy_f64(tmp_path):
    # host-side numpy reference computations are f64 by default — only
    # jax-side wide dtypes are in scope
    found = _lint(tmp_path, """
        import numpy as np

        def reference(x):
            return np.asarray(x, np.float64).sum()
    """)
    assert _only(found, "RPL401") == []


# =====================================================================
# RPL402 bf16-accum
# =====================================================================

def test_rpl402_sum_over_bf16_cast(tmp_path):
    found = _lint(tmp_path, """
        import jax.numpy as jnp

        def f(x):
            return jnp.sum(x.astype(jnp.bfloat16))
    """)
    hits = _only(found, "RPL402")
    assert len(hits) == 1 and "sum" in hits[0].message


def test_rpl402_matmul_operator_on_f16(tmp_path):
    found = _lint(tmp_path, """
        import jax.numpy as jnp

        def f(a, b):
            return a.astype(jnp.float16) @ b
    """)
    hits = _only(found, "RPL402")
    assert len(hits) == 1 and "matmul" in hits[0].message


def test_rpl402_negative_wide_accumulator(tmp_path):
    found = _lint(tmp_path, """
        import jax.numpy as jnp

        def f(x, a, b):
            s = jnp.sum(x.astype(jnp.bfloat16), dtype=jnp.float32)
            m = jnp.matmul(a.astype(jnp.bfloat16), b,
                           preferred_element_type=jnp.float32)
            t = jnp.sum(x.astype(jnp.float32))
            return s, m, t
    """)
    assert _only(found, "RPL402") == []


# =====================================================================
# RPL501 problem-hooks
# =====================================================================

def test_rpl501_missing_full_step(tmp_path):
    found = _lint(tmp_path, """
        from repro.core.problem import Problem, register

        @register("fixture_a")
        class A(Problem):
            def init_bundle(self, inputs, mesh):
                return None
    """)
    hits = _only(found, "RPL501")
    assert len(hits) == 1 and "full_step" in hits[0].message


def test_rpl501_wrong_hook_arity(tmp_path):
    found = _lint(tmp_path, """
        from repro.core.problem import Problem, register

        @register("fixture_b")
        class B(Problem):
            def init_bundle(self, inputs):      # lost the mesh param
                return None

            def full_step(self, d, rep, axes, extra):
                return d, 0.0
    """)
    hits = _only(found, "RPL501")
    assert len(hits) == 2
    assert all("DESIGN.md" in h.message for h in hits)


def test_rpl501_negative_conforming_class(tmp_path):
    found = _lint(tmp_path, """
        from repro.core.problem import Problem, register

        @register("fixture_c")
        class C(Problem):
            replicated_in_carry = True

            def init_bundle(self, inputs, mesh):
                return None

            def full_step(self, d, rep, axes):
                return d, 0.0

            def light_step(self, d, rep, axes):
                return d, 0.0

            def refresh_replicated(self, rep, out):
                return rep

        class NotRegistered:
            def init_bundle(self):      # not @register-ed: out of scope
                pass
    """)
    assert _only(found, "RPL501") == []
    assert _only(found, "RPL502") == []


# =====================================================================
# RPL502 problem-metadata
# =====================================================================

def test_rpl502_replicated_without_refresh(tmp_path):
    found = _lint(tmp_path, """
        from repro.core.problem import Problem, register

        @register("fixture_d")
        class D(Problem):
            replicated_in_carry = True

            def init_bundle(self, inputs, mesh):
                return None

            def full_step(self, d, rep, axes):
                return d, 0.0
    """)
    hits = _only(found, "RPL502")
    assert len(hits) == 2       # needs refresh_replicated AND light_step
    msgs = " | ".join(h.message for h in hits)
    assert "refresh_replicated" in msgs and "light_step" in msgs


def test_rpl502_refresh_without_flag_and_chunk_without_cost(tmp_path):
    found = _lint(tmp_path, """
        from repro.core.problem import Problem, register

        @register("fixture_e")
        class E(Problem):
            default_cost_every = "chunk"

            def init_bundle(self, inputs, mesh):
                return None

            def full_step(self, d, rep, axes):
                return d, 0.0

            def refresh_replicated(self, rep, out):
                return rep
    """)
    hits = _only(found, "RPL502")
    msgs = " | ".join(h.message for h in hits)
    assert "dead wiring" in msgs          # refresh without the flag
    assert "chunk" in msgs                # cost_every="chunk" without cost


# =====================================================================
# RPL601 noncanonical-import
# =====================================================================

def test_rpl601_auto_interpret_via_kernel_reexport(tmp_path):
    found = _lint(tmp_path, """
        from repro.kernels.condat_elwise.kernel import auto_interpret
    """)
    hits = _only(found, "RPL601")
    assert len(hits) == 1 and "repro.kernels.common" in hits[0].message


def test_rpl601_pad_leading_via_ops(tmp_path):
    found = _lint(tmp_path, """
        from repro.kernels.dict_outer.ops import pad_leading
    """)
    assert len(_only(found, "RPL601")) == 1


def test_rpl601_negative_canonical_import(tmp_path):
    found = _lint(tmp_path, """
        from repro.kernels.common import auto_interpret, pad_leading
        from repro.kernels.dict_outer.kernel import dict_outer_fwd
    """)
    assert _only(found, "RPL601") == []


# =====================================================================
# Suppressions
# =====================================================================

_SUPPRESSIBLE = """
    import jax

    @jax.jit
    def f(x):
        if x > 0:{comment}
            return x
        return -x
"""


def test_suppression_by_rule_id(tmp_path):
    src = _SUPPRESSIBLE.format(comment="  # repro-lint: disable=RPL301")
    assert _only(_lint(tmp_path, src), "RPL301") == []


def test_suppression_by_slug(tmp_path):
    src = _SUPPRESSIBLE.format(
        comment="  # repro-lint: disable=traced-branch")
    assert _only(_lint(tmp_path, src), "RPL301") == []


def test_suppression_file_wide(tmp_path):
    src = "# repro-lint: disable-file=RPL301\n" + \
        textwrap.dedent(_SUPPRESSIBLE.format(comment=""))
    p = tmp_path / "mod.py"
    p.write_text(src)
    assert _only(lint_file(p), "RPL301") == []


def test_suppression_only_hides_named_rule(tmp_path):
    src = _SUPPRESSIBLE.format(comment="  # repro-lint: disable=RPL999")
    assert len(_only(_lint(tmp_path, src), "RPL301")) == 1


# =====================================================================
# RPL701 swallowed-exception
# =====================================================================

def test_rpl701_bare_except_pass_in_core(tmp_path):
    found = _lint(tmp_path, """
        def load(path):
            try:
                return open(path).read()
            except:
                pass
    """, name="repro/core/loader.py")
    hits = _only(found, "RPL701")
    assert len(hits) == 1
    assert hits[0].line == 5
    assert "bare except" in hits[0].message


def test_rpl701_broad_except_logged_only_in_checkpoint(tmp_path):
    found = _lint(tmp_path, """
        def write(step, tree):
            try:
                _do_write(step, tree)
            except Exception as e:
                print("checkpoint write failed:", e)
    """, name="repro/checkpoint/writer.py")
    hits = _only(found, "RPL701")
    assert len(hits) == 1 and "except Exception" in hits[0].message


def test_rpl701_broad_tuple_except_in_resilience(tmp_path):
    found = _lint(tmp_path, """
        def step(fn):
            try:
                return fn()
            except (OSError, BaseException):
                return None
    """, name="repro/resilience/loop.py")
    assert len(_only(found, "RPL701")) == 1


def test_rpl701_reraise_and_router_are_clean(tmp_path):
    found = _lint(tmp_path, """
        from repro.resilience.errors import classify

        def dispatch(self, fn):
            try:
                return fn()
            except Exception as e:
                if classify(e) != "transient":
                    raise
                self.retries += 1

        def background(self, fn):
            try:
                fn()
            except BaseException as e:
                self._record_failure(e)
    """, name="repro/core/supervised.py")
    assert _only(found, "RPL701") == []


def test_rpl701_narrow_except_is_clean(tmp_path):
    found = _lint(tmp_path, """
        def parse(text):
            try:
                return int(text)
            except ValueError:
                return None
    """, name="repro/core/parse.py")
    assert _only(found, "RPL701") == []


def test_rpl701_out_of_scope_not_flagged(tmp_path):
    found = _lint(tmp_path, """
        def probe():
            try:
                return _compile()
            except Exception:
                return None
    """, name="repro/kernels/probe.py")
    assert _only(found, "RPL701") == []


# =====================================================================
# RPL801 batch-axes
# =====================================================================

def test_rpl801_undeclared_constructor_state(tmp_path):
    found = _lint(tmp_path, """
        from repro.core.problem import Problem, register

        @register("toy")
        class Toy(Problem):
            def __init__(self, cfg=None, sigma=0.02):
                self.cfg = cfg
                self.sigma = sigma

            def init_bundle(self, inputs, mesh):
                return build(inputs, self.cfg, noise=self.sigma)

            def full_step(self, d, rep, axes):
                return d, {"cost": 0.0}

            def batch_axes(self):
                from repro.core.batching import BatchAxes
                return BatchAxes(record_axes=0)
    """)
    hits = _only(found, "RPL801")
    assert len(hits) == 1
    assert "self.sigma" in hits[0].message
    assert "instance_invariant" in hits[0].message


def test_rpl801_missing_batch_axes_declaration(tmp_path):
    found = _lint(tmp_path, """
        from repro.core.problem import Problem, register

        @register("toy")
        class Toy(Problem):
            def __init__(self, key=None):
                self.key = key

            def init_bundle(self, inputs, mesh):
                return build(inputs, key=self.key, cfg=self.cfg)

            def full_step(self, d, rep, axes):
                return d, {"cost": 0.0}
    """)
    hits = _only(found, "RPL801")
    assert len(hits) == 1
    assert "declares no batch_axes()" in hits[0].message
    assert "key" in hits[0].message


def test_rpl801_declared_state_is_clean(tmp_path):
    found = _lint(tmp_path, """
        from repro.core.batching import BatchAxes
        from repro.core.problem import Problem, register

        @register("toy")
        class Toy(Problem):
            def __init__(self, cfg=None, key=None):
                self.cfg = cfg
                self.key = key
                self._cache = None

            def init_bundle(self, inputs, mesh):
                return build(inputs, self.cfg, key=self.key,
                             helper=self.helper())

            def helper(self):
                return 1

            def full_step(self, d, rep, axes):
                return d, {"cost": 0.0}

            def batch_axes(self):
                return BatchAxes(record_axes=0,
                                 instance_invariant=("key",))
    """)
    assert _only(found, "RPL801") == []


def test_rpl801_unregistered_class_not_flagged(tmp_path):
    found = _lint(tmp_path, """
        class Helper:
            def __init__(self, sigma):
                self.sigma = sigma

            def init_bundle(self, inputs, mesh):
                return build(inputs, self.sigma)
    """)
    assert _only(found, "RPL801") == []


# =====================================================================
# RPL901 untracked-task
# =====================================================================

def test_rpl901_bare_create_task(tmp_path):
    found = _lint(tmp_path, """
        import asyncio

        async def start(self):
            asyncio.create_task(self._watchdog())
    """, name="repro/serve/svc.py")
    hits = _only(found, "RPL901")
    assert len(hits) == 1
    assert "discards the task handle" in hits[0].message


def test_rpl901_assigned_never_used(tmp_path):
    found = _lint(tmp_path, """
        import asyncio

        async def start(self):
            t = asyncio.ensure_future(self._watchdog())
            return self
    """, name="repro/serve/svc.py")
    hits = _only(found, "RPL901")
    assert len(hits) == 1
    assert "'t'" in hits[0].message


def test_rpl901_tracked_handles_clean(tmp_path):
    found = _lint(tmp_path, """
        import asyncio

        async def start(self):
            # stored on the object: cancellable and inspectable
            self._watchdog_task = asyncio.create_task(self._watchdog())
            self._watchdog_task.add_done_callback(self._task_exc)

        async def probe(self, coros):
            # awaited and gathered handles retrieve their exceptions
            t = asyncio.create_task(coros[0])
            await t
            rest = [asyncio.ensure_future(c) for c in coros[1:]]
            return await asyncio.gather(*rest)
    """, name="repro/serve/svc.py")
    assert _only(found, "RPL901") == []


def test_rpl901_out_of_scope_clean(tmp_path):
    found = _lint(tmp_path, """
        import asyncio

        async def fire_and_forget(coro):
            asyncio.create_task(coro)
    """, name="repro/core/loop.py")
    assert _only(found, "RPL901") == []


# =====================================================================
# Registry / CLI / output contracts
# =====================================================================

def test_rule_ids_stable():
    ids = {r.id: r.slug for r in all_rules()}
    assert ids == {
        "RPL101": "donated-reuse",
        "RPL201": "blockspec-grid",
        "RPL202": "missing-interpret",
        "RPL203": "ref-parity",
        "RPL301": "traced-branch",
        "RPL302": "host-cast",
        "RPL303": "numpy-on-traced",
        "RPL401": "f64-dtype",
        "RPL402": "bf16-accum",
        "RPL501": "problem-hooks",
        "RPL502": "problem-metadata",
        "RPL601": "noncanonical-import",
        "RPL701": "swallowed-exception",
        "RPL801": "batch-axes",
        "RPL901": "untracked-task",
    }


def test_finding_format_is_path_line_col(tmp_path):
    found = _lint(tmp_path, """
        from repro.kernels.dict_outer.kernel import auto_interpret
    """)
    line = found[0].format()
    import re
    assert re.match(
        r"^.+mod\.py:\d+:\d+: RPL\d{3}\[[a-z0-9-]+\] .+", line), line


def test_syntax_error_reported_not_raised(tmp_path):
    found = _lint(tmp_path, "def broken(:\n")
    assert [f.rule.id for f in found] == ["RPL000"]


def test_cli_exit_codes_and_report(tmp_path, capsys):
    dirty = tmp_path / "dirty.py"
    dirty.write_text(
        "from repro.kernels.dict_outer.kernel import auto_interpret\n")
    clean = tmp_path / "clean.py"
    clean.write_text("x = 1\n")
    report = tmp_path / "report.json"

    assert lint_main([str(dirty), "--report", str(report)]) == 1
    out = capsys.readouterr().out
    assert "RPL601" in out and "1 finding" in out
    data = json.loads(report.read_text())
    assert data["findings"][0]["rule"] == "RPL601"
    assert {r["id"] for r in data["rules"]} >= {"RPL101", "RPL601"}

    assert lint_main([str(clean)]) == 0
    assert lint_main(["--list-rules"]) == 0
    assert "RPL301" in capsys.readouterr().out


def test_cli_select_filters_rules(tmp_path, capsys):
    dirty = tmp_path / "dirty.py"
    dirty.write_text(
        "from repro.kernels.dict_outer.kernel import auto_interpret\n")
    assert lint_main([str(dirty), "--select", "RPL101"]) == 0
    assert lint_main([str(dirty), "--select", "noncanonical-import"]) == 1


# =====================================================================
# Acceptance: the repo's own tree lints clean
# =====================================================================

def test_repo_tree_lints_clean():
    findings = lint_paths([REPO / "src", REPO / "tests",
                           REPO / "benchmarks"])
    assert findings == [], "\n".join(f.format() for f in findings)

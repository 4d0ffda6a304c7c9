"""tools/analyze — the repo-native static-analysis suite (ISSUE 1).

Three layers:
1. the tier-1 gate: a clean run over the REAL tree (any finding fails),
2. seeded-bug fixtures: every rule demonstrably fires on a known-bad
   snippet and stays silent on the corresponding fixed shape,
3. ADVICE r5 regression demos: the literal pre-fix patterns from the
   four advisor findings, each caught by its rule.
"""

import os
import textwrap

import pytest

from tools.analyze import repo_root, run_all
from tools.analyze.abi import check_abi, check_float_casts
from tools.analyze.collectives import check_collectives_file
from tools.analyze.common import Finding, apply_suppressions
from tools.analyze.hygiene import check_hygiene_file
from tools.analyze.obs_rules import check_obs, check_obs_file
from tools.analyze.perf_rules import check_perf, check_perf_file
from tools.analyze.predict_rules import check_predict, check_predict_file
from tools.analyze.quantize_rules import check_quantize_file
from tools.analyze.serving_rules import check_serving, check_serving_file
from tools.analyze.tracer import check_host_only_file, check_tracer_file


def rules(findings):
    return [f.rule for f in findings]


def _write(path, text):
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as fh:
        fh.write(textwrap.dedent(text))
    return path


def _abi_tree(tmp_path, cpp=None, py=None):
    """A minimal root/mmlspark_tpu/native tree for check_abi."""
    root = str(tmp_path)
    native = os.path.join(root, "mmlspark_tpu", "native")
    for name, text in (cpp or {}).items():
        _write(os.path.join(native, name), text)
    for name, text in (py or {}).items():
        _write(os.path.join(native, name), text)
    return root


# ---------------------------------------------------------------- tier-1


def test_real_tree_is_clean():
    findings = run_all(repo_root())
    assert findings == [], "\n".join(str(f) for f in findings)


# ------------------------------------------------------------ ABI fixtures


def test_abi001_platform_width_c_type(tmp_path):
    root = _abi_tree(tmp_path, cpp={"k.cpp": """
        extern "C" {
        void f(const double* x, long n);
        }
    """})
    found = check_abi(root)
    assert "ABI001" in rules(found)
    assert "int64_t" in next(f for f in found if f.rule == "ABI001").message


def test_abi001_silent_on_fixed_width(tmp_path):
    root = _abi_tree(tmp_path, cpp={"k.cpp": """
        extern "C" {
        void f(const double* x, int64_t n);
        }
    """})
    assert "ABI001" not in rules(check_abi(root))


def test_abi002_platform_width_ctypes(tmp_path):
    root = _abi_tree(tmp_path, py={"b.py": """
        import ctypes
        def bind(lib):
            lib.f.argtypes = [ctypes.c_long, ctypes.POINTER(ctypes.c_longlong)]
            lib.f.restype = None
    """})
    found = [f for f in check_abi(root) if f.rule == "ABI002"]
    assert len(found) == 2  # both the scalar and the pointer


def test_abi003_arity_mismatch(tmp_path):
    root = _abi_tree(
        tmp_path,
        cpp={"k.cpp": """
            extern "C" {
            void f(const double* x, int64_t n, int threads);
            }
        """},
        py={"b.py": """
            import ctypes
            def bind(lib):
                lib.f.argtypes = [ctypes.POINTER(ctypes.c_double),
                                  ctypes.c_int64]
                lib.f.restype = None
        """},
    )
    assert "ABI003" in rules(check_abi(root))


def test_abi004_per_arg_and_restype_mismatch(tmp_path):
    root = _abi_tree(
        tmp_path,
        cpp={"k.cpp": """
            extern "C" {
            int64_t f(const double* x, int64_t n, const int64_t* cols);
            }
        """},
        py={"b.py": """
            import ctypes
            def bind(lib):
                lib.f.argtypes = [ctypes.POINTER(ctypes.c_double),
                                  ctypes.c_int,          # width mismatch
                                  ctypes.c_int64]        # pointer-depth
                lib.f.restype = None                     # restype mismatch
        """},
    )
    found = [f for f in check_abi(root) if f.rule == "ABI004"]
    assert len(found) == 3
    msgs = " ".join(f.message for f in found)
    assert "arg 2" in msgs and "arg 3" in msgs and "restype" in msgs


def test_abi004_silent_when_binding_matches(tmp_path):
    root = _abi_tree(
        tmp_path,
        cpp={"k.cpp": """
            extern "C" {
            void* f(const char* text, int64_t n, uint8_t* out);
            }
        """},
        py={"b.py": """
            import ctypes
            def bind(lib):
                lib.f.argtypes = [ctypes.c_char_p, ctypes.c_int64,
                                  ctypes.POINTER(ctypes.c_uint8)]
                lib.f.restype = ctypes.c_void_p
        """},
    )
    assert rules(check_abi(root)) == []


def test_abi005_decl_sites_disagree(tmp_path):
    root = _abi_tree(tmp_path, cpp={
        "k.cpp": """
            extern "C" {
            void f(const double* x, int64_t n) { (void)x; (void)n; }
            }
        """,
        "harness.cpp": """
            extern "C" {
            void f(const double*, int);
            }
        """,
    })
    found = [f for f in check_abi(root) if f.rule == "ABI005"]
    assert len(found) == 1
    assert found[0].file.endswith("k.cpp") or found[0].file.endswith(
        "harness.cpp")


def test_abi_resolves_getattr_bound_symbols(tmp_path):
    # the repo's own idiom: optional symbol via getattr + local alias
    root = _abi_tree(
        tmp_path,
        cpp={"k.cpp": """
            extern "C" {
            void g(const int64_t* cols, int64_t n);
            }
        """},
        py={"b.py": """
            import ctypes
            def bind(lib):
                fn = getattr(lib, "g", None)
                if fn is not None:
                    p = ctypes.POINTER(ctypes.c_int64)
                    fn.argtypes = [p, ctypes.c_int]
                    fn.restype = None
        """},
    )
    found = [f for f in check_abi(root) if f.rule == "ABI004"]
    assert len(found) == 1 and "arg 2" in found[0].message


def test_nat001_unclamped_float_cast(tmp_path):
    p = _write(str(tmp_path / "k.cpp"), """
        extern "C" {
        void t(const double* row, uint8_t* out) {
          const double x = row[0];
          int64_t v = static_cast<int64_t>(x);
          out[0] = v > 0;
        }
        }
    """)
    found = check_float_casts(p)
    assert rules(found) == ["NAT001"]


def test_nat001_silent_with_clamp(tmp_path):
    p = _write(str(tmp_path / "k.cpp"), """
        extern "C" {
        void t(const double* row, uint8_t* out) {
          const double x = row[0];
          int64_t v;
          if (x >= 9223372036854775808.0) {
            v = 0;
          } else {
            v = static_cast<int64_t>(x);
          }
          out[0] = v > 0;
        }
        }
    """)
    assert check_float_casts(p) == []


def test_nat001_silent_on_integer_cast(tmp_path):
    p = _write(str(tmp_path / "k.cpp"), """
        void h() {
          int64_t n = 7;
          size_t m = static_cast<size_t>(n);
          (void)m;
        }
    """)
    assert check_float_casts(p) == []


# ----------------------------------------------------- collective fixtures


def test_col001_process_count_gate_without_evidence(tmp_path):
    p = _write(str(tmp_path / "m.py"), """
        import jax
        def agree(local_ok):
            if jax.process_count() == 1:
                return local_ok
            flags = host_allgather([1 if local_ok else 0])
            return min(flags)
    """)
    found = check_collectives_file(p)
    assert rules(found) == ["COL001"]


def test_col001_silent_with_multi_controller_evidence(tmp_path):
    # the FIXED trace_cache shape: evidence token in the guard chain
    p = _write(str(tmp_path / "m.py"), """
        import jax
        def agree(local_ok, multi_controller):
            if not multi_controller or jax.process_count() == 1:
                return local_ok
            flags = host_allgather([1 if local_ok else 0])
            return min(flags)
    """)
    assert check_collectives_file(p) == []


def test_col001_silent_on_unconditional_collective(tmp_path):
    # no rank-dependent guard = an all-ranks caller contract, not a bug
    p = _write(str(tmp_path / "m.py"), """
        def merge(x):
            return host_allgather_ragged_rows(x)
    """)
    assert check_collectives_file(p) == []


def test_col001_ternary_guard(tmp_path):
    p = _write(str(tmp_path / "m.py"), """
        import jax
        def total(x):
            return host_allgather([len(x)]).sum() if jax.process_count() > 1 else len(x)
    """)
    assert rules(check_collectives_file(p)) == ["COL001"]


def test_col002_mismatched_branch_sequences(tmp_path):
    p = _write(str(tmp_path / "m.py"), """
        def stats(x, fast):
            if fast:
                a = host_allgather(x)
                b = host_allgather_ragged_rows(x)
            else:
                b = host_allgather_ragged_rows(x)
                a = host_allgather(x)
            return a, b
    """)
    assert rules(check_collectives_file(p)) == ["COL002"]


def test_col002_silent_when_sequences_match(tmp_path):
    p = _write(str(tmp_path / "m.py"), """
        def stats(x, fast):
            if fast:
                a = host_allgather(x + 1)
            else:
                a = host_allgather(x - 1)
            return a
    """)
    assert check_collectives_file(p) == []


def test_col003_rank_pinned_guard(tmp_path):
    p = _write(str(tmp_path / "m.py"), """
        import jax
        def save(x):
            if jax.process_index() == 0:
                host_allgather(x)
    """)
    assert rules(check_collectives_file(p)) == ["COL003"]


def test_col004_full_histogram_psum(tmp_path):
    # the pre-ISSUE-4 merge shape: every device receives all F×B floats
    p = _write(str(tmp_path / "m.py"), """
        from jax import lax
        def merge(hist, axis_name):
            return lax.psum(hist, axis_name)
    """)
    assert rules(check_collectives_file(p)) == ["COL004"]


def test_col004_bare_name_and_derived_operand(tmp_path):
    p = _write(str(tmp_path / "m.py"), """
        from jax.lax import psum
        def merge(hists_local, axis_name):
            return psum(hists_local.astype("bfloat16"), axis_name)
    """)
    assert rules(check_collectives_file(p)) == ["COL004"]


def test_col004_silent_on_sanctioned_paths(tmp_path):
    # the reduce-scatter helper, non-histogram psums, and psum_scatter
    # itself are all fine
    p = _write(str(tmp_path / "m.py"), """
        from jax import lax
        def merge(hist, grad_tot, axis_name):
            a = device_psum_scatter(hist, axis_name, scatter_dimension=1)
            b = lax.psum(grad_tot, axis_name)
            c = lax.psum_scatter(hist, axis_name, scatter_dimension=1)
            d = device_psum(hist, axis_name)
            return a, b, c, d
    """)
    assert check_collectives_file(p) == []


def test_col004_suppression(tmp_path):
    # voting's elected-slice psum: operand is already a reduced slice
    p = _write(str(tmp_path / "m.py"), """
        from jax import lax
        def merge(hists_sel, axis_name):
            return lax.psum(hists_sel, axis_name)  # analyze: ignore[COL004]
    """)
    assert apply_suppressions(check_collectives_file(p)) == []


def test_col004_library_voting_site_is_suppressed():
    # the one sanctioned raw psum-of-histograms in the package carries the
    # inline suppression; the analyzer stays clean over mmlspark_tpu/
    import tools.analyze.collectives as col

    root = os.path.dirname(os.path.dirname(os.path.abspath(col.__file__)))
    repo = os.path.dirname(root)
    found = apply_suppressions(col.check_collectives(repo))
    assert [f for f in found if f.rule == "COL004"] == []


def test_col007_full_hist_over_inter_axis(tmp_path):
    # the ISSUE 14 shape: the full (F,...) histogram crossing the slow
    # inter-host axis, spelled via the DATA_AXIS constant or the literal
    p = _write(str(tmp_path / "m.py"), """
        from mmlspark_tpu.parallel.mesh import DATA_AXIS
        def merge(hist):
            a = device_psum(hist, axis_name=DATA_AXIS)
            b = device_all_gather(hist, "data")
            return a, b
    """)
    assert rules(check_collectives_file(p)) == ["COL007", "COL007"]


def test_col007_silent_on_reduced_or_parameterized(tmp_path):
    # scattered/sliced/winner operands and parameterized axes stay quiet:
    # the rule targets hardcoded slow-axis call sites with full-F payloads
    p = _write(str(tmp_path / "m.py"), """
        from mmlspark_tpu.parallel.mesh import DATA_AXIS
        def merge(hist, hist_win_col, hist_scattered, axis_name):
            a = device_psum(hist_win_col, axis_name=DATA_AXIS)
            b = device_psum(hist_scattered, axis_name=DATA_AXIS)
            c = device_psum(hist, axis_name)
            d = device_psum_scatter(hist, DATA_AXIS, scatter_dimension=1)
            e = device_psum(grad_tot, axis_name=DATA_AXIS)
            return a, b, c, d, e
    """)
    assert [f for f in check_collectives_file(p) if f.rule == "COL007"] == []


def test_col007_suppression_round_trip(tmp_path):
    p = _write(str(tmp_path / "m.py"), """
        def merge(hist):
            return device_psum(hist, axis_name="data")  # analyze: ignore[COL007]
    """)
    found = check_collectives_file(p)
    assert rules(found) == ["COL007"]
    assert apply_suppressions(found) == []


def test_col007_real_tree_clean():
    # the hierarchical merge keeps every full-F payload off the slow axis;
    # the package must carry zero (unsuppressed) COL007 findings
    import tools.analyze.collectives as col

    root = os.path.dirname(os.path.dirname(os.path.abspath(col.__file__)))
    repo = os.path.dirname(root)
    found = apply_suppressions(col.check_collectives(repo))
    assert [f for f in found if f.rule == "COL007"] == []


# --------------------------------------------------------- tracer fixtures


def test_trc001_if_on_traced_param(tmp_path):
    p = _write(str(tmp_path / "m.py"), """
        import jax
        @jax.jit
        def f(x):
            if x > 0:
                return x
            return -x
    """)
    assert rules(check_tracer_file(p)) == ["TRC001"]


def test_trc001_while_and_jit_call_form(tmp_path):
    p = _write(str(tmp_path / "m.py"), """
        import jax
        def outer():
            def g(x):
                while x < 10:
                    x = x * 2
                return x
            return jax.jit(g)
    """)
    assert rules(check_tracer_file(p)) == ["TRC001"]


def test_trc001_silent_on_static_tests(tmp_path):
    p = _write(str(tmp_path / "m.py"), """
        import jax
        from functools import partial
        @partial(jax.jit, static_argnames=("k",))
        def f(x, y, k):
            if x.shape[0] > 2:     # shapes are static
                y = y + 1
            if y is None:          # identity, not value
                return x
            if len(x) > 3:         # len is static
                y = y * 2
            if k:                  # static_argnames-exempt
                return y
            return x + y
    """)
    assert check_tracer_file(p) == []


def test_trc002_np_call_on_traced_arg(tmp_path):
    p = _write(str(tmp_path / "m.py"), """
        import jax
        import numpy as np
        @jax.jit
        def f(x):
            return np.sum(x)
    """)
    assert rules(check_tracer_file(p)) == ["TRC002"]


def test_trc002_silent_on_np_constants(tmp_path):
    p = _write(str(tmp_path / "m.py"), """
        import jax
        import numpy as np
        @jax.jit
        def f(x):
            return x + np.float32(1.5) + np.zeros(3)
    """)
    assert check_tracer_file(p) == []


def test_trc003_jnp_in_host_only_module(tmp_path):
    p = _write(str(tmp_path / "frame.py"), """
        import jax.numpy as jnp
        def to_cols(df):
            return jnp.asarray(df)
    """)
    assert rules(check_host_only_file(p)) == ["TRC003"]
    clean = _write(str(tmp_path / "frame2.py"), """
        import numpy as np
        def to_cols(df):
            return np.asarray(df)
    """)
    assert check_host_only_file(clean) == []


# -------------------------------------------------------- hygiene fixtures


def test_hyg001_atime_eviction_without_utime(tmp_path):
    p = _write(str(tmp_path / "cache.py"), """
        import os
        def prune(path):
            entries = []
            with os.scandir(path) as it:
                for e in it:
                    st = e.stat()
                    entries.append((st.st_atime, e.path))
            for _, p in sorted(entries)[:-10]:
                os.remove(p)
    """)
    assert rules(check_hygiene_file(p)) == ["HYG001"]


def test_hyg001_silent_with_utime_on_hit(tmp_path):
    p = _write(str(tmp_path / "cache.py"), """
        import os
        def record_hit(path):
            os.utime(path)
        def prune(path):
            entries = []
            with os.scandir(path) as it:
                for e in it:
                    st = e.stat()
                    entries.append((max(st.st_atime, st.st_mtime), e.path))
            for _, p in sorted(entries)[:-10]:
                os.remove(p)
    """)
    assert check_hygiene_file(p) == []


# ------------------------------------------------------------ obs fixtures


def test_obs001_bare_print_in_library_code(tmp_path):
    p = _write(str(tmp_path / "mmlspark_tpu" / "engine" / "m.py"), """
        def fit(x, verbose):
            if verbose:
                print("iteration", x)
            return x
    """)
    found = check_obs_file(p)
    assert rules(found) == ["OBS001"]
    assert "obs logger" in found[0].message
    # the tree walker only visits mmlspark_tpu/, so the same snippet under
    # tests/ or tools/ never fires
    _write(str(tmp_path / "tests" / "t.py"), "print('assert output')\n")
    _write(str(tmp_path / "tools" / "u.py"), "print('cli output')\n")
    assert rules(check_obs(str(tmp_path))) == ["OBS001"]


def test_obs001_silent_on_logger_and_shadowed_print(tmp_path):
    p = _write(str(tmp_path / "m.py"), """
        from mmlspark_tpu import obs
        def fit(x):
            obs.get_logger().info("iteration %s", x)
            return x
        def render(print):           # a local named print is not a call
            return print
    """)
    assert check_obs_file(p) == []


def test_obs001_suppression_round_trip(tmp_path):
    src = """
        def show(df):
            print(df.head()){supp}
    """
    fires = _write(str(tmp_path / "a.py"), src.format(supp=""))
    assert rules(apply_suppressions(check_obs_file(fires))) == ["OBS001"]
    silenced = _write(str(tmp_path / "b.py"),
                      src.format(supp="  # analyze: ignore[OBS001]"))
    assert apply_suppressions(check_obs_file(silenced)) == []


def test_obs002_span_drops_trace_context(tmp_path):
    # Seeded bug: request-handling functions (they take items/rid) opening
    # spans without any trace attr — tools.obs trace can never join them.
    p = _write(str(tmp_path / "mmlspark_tpu" / "serve" / "m.py"), """
        from mmlspark_tpu import obs
        def process(route, items):
            with obs.span("serve.batch", model=route):
                pass
            for item in items:
                obs.record_span("serve.reply", 0.1)
    """)
    found = check_obs_file(p)
    assert rules(found) == ["OBS002", "OBS002"]
    assert "trace" in found[0].message


def test_obs002_silent_when_trace_propagated(tmp_path):
    p = _write(str(tmp_path / "mmlspark_tpu" / "parallel" / "m.py"), """
        from mmlspark_tpu import obs
        def process(items):
            with obs.span("serve.batch", members=[i.rid for i in items]):
                pass
            obs.record_span("serve.reply", 0.1, rid="r1")
        def scorer(rid, X):
            with obs.span("predict", rows=len(X), **obs.trace_attrs()):
                return X
        def plain(X):  # no request-scoped params: rule does not apply
            with obs.span("serve.prewarm", bucket=8):
                return X
    """)
    assert check_obs_file(p) == []


def test_obs002_only_fires_in_hot_path_dirs(tmp_path):
    src = """
        from mmlspark_tpu import obs
        def fit(item):
            with obs.span("booster.iteration"):
                return item
    """
    outside = _write(str(tmp_path / "mmlspark_tpu" / "engine" / "m.py"), src)
    assert check_obs_file(outside) == []
    inside = _write(str(tmp_path / "mmlspark_tpu" / "serve" / "m.py"), src)
    assert rules(check_obs_file(inside)) == ["OBS002"]


def test_obs002_suppression_round_trip(tmp_path):
    src = """
        from mmlspark_tpu import obs
        def handle(rid):{supp}
            with obs.span("serve.anon"):
                pass
    """
    base = str(tmp_path / "mmlspark_tpu" / "serve")
    fires = _write(os.path.join(base, "a.py"), src.format(supp=""))
    assert rules(apply_suppressions(check_obs_file(fires))) == ["OBS002"]
    silenced = _write(
        os.path.join(base, "b.py"),
        src.format(supp="  # analyze: ignore[OBS002]"),
    )
    assert apply_suppressions(check_obs_file(silenced)) == []


def test_obs003_unbounded_request_keyed_growth(tmp_path):
    # Seeded bug: per-request dict/list on self with no cap — the serve
    # process grows memory forever under request traffic.
    p = _write(str(tmp_path / "mmlspark_tpu" / "serve" / "m.py"), """
        class Tracker:
            def handle(self, rid, req):
                self._seen[rid] = req
                self._log.append(rid)
    """)
    found = check_obs_file(p)
    assert rules(found) == ["OBS003", "OBS003"]
    assert "request-derived" in found[0].message
    assert "rid" in found[0].message


def test_obs003_taints_one_assignment_hop(tmp_path):
    # The key is derived from a request param through one assignment —
    # still request-cardinality, still fires.
    p = _write(str(tmp_path / "mmlspark_tpu" / "obs" / "m.py"), """
        class Reg:
            def count(self, labels):
                k = (1, tuple(labels))
                self._counters[k] = 1
    """)
    assert rules(check_obs_file(p)) == ["OBS003"]


def test_obs003_silent_on_bounded_shapes(tmp_path):
    p = _write(str(tmp_path / "mmlspark_tpu" / "serve" / "m.py"), """
        class Tracker:
            def capped(self, rid, req):
                if len(self._seen) < self._max_series:
                    self._seen[rid] = req
            def guarded(self, rid, req):
                if not self._admit(rid):
                    return
                self._seen[rid] = req
            def evicting(self, rid, req):
                self._seen[rid] = req
                while len(self._seen) > 10:
                    self._seen.popitem()
            def local_only(self, items):
                out = []
                for item in items:
                    out.append(item)
                return out
    """)
    assert check_obs_file(p) == []


def test_obs003_only_fires_in_obs_and_serve_dirs(tmp_path):
    src = """
        class T:
            def handle(self, rid):
                self._seen[rid] = 1
    """
    outside = _write(str(tmp_path / "mmlspark_tpu" / "engine" / "m.py"), src)
    assert check_obs_file(outside) == []
    inside = _write(str(tmp_path / "mmlspark_tpu" / "obs" / "m.py"), src)
    assert rules(check_obs_file(inside)) == ["OBS003"]


def test_obs003_suppression_round_trip(tmp_path):
    src = """
        class T:
            def register(self, rid, model):
                self._routes[rid] = model{supp}
    """
    base = str(tmp_path / "mmlspark_tpu" / "serve")
    fires = _write(os.path.join(base, "a.py"), src.format(supp=""))
    assert rules(apply_suppressions(check_obs_file(fires))) == ["OBS003"]
    silenced = _write(
        os.path.join(base, "b.py"),
        src.format(supp="  # analyze: ignore[OBS003]"),
    )
    assert apply_suppressions(check_obs_file(silenced)) == []


def test_obs004_wall_clock_duration(tmp_path):
    # Seeded bug: steps/budget durations from differenced time.time() —
    # NTP slew makes them jump or go negative.
    p = _write(str(tmp_path / "m.py"), """
        import time
        def fit(X):
            t0 = time.time()
            run(X)
            dur = time.time() - t0
            return dur
    """)
    found = check_obs_file(p)
    # both the call-operand subtraction and the tainted-name operand fire
    assert rules(found) == ["OBS004"]
    assert "monotonic" in found[0].message


def test_obs004_silent_on_monotonic_and_timestamps(tmp_path):
    p = _write(str(tmp_path / "m.py"), """
        import time
        def fit(X):
            t0 = time.perf_counter()
            run(X)
            dur = time.perf_counter() - t0          # monotonic: fine
            rec = {"ts": time.time(), "dur": dur}   # timestamp: fine
            return rec
        def other(a, b):
            t0 = 5.0
            return a - t0   # untainted name sharing a timestamp spelling
    """)
    assert check_obs_file(p) == []


def test_obs004_scopes_do_not_leak(tmp_path):
    # a metadata timestamp in one function must not taint a subtraction
    # over the same name in another
    p = _write(str(tmp_path / "m.py"), """
        import time
        def stamp():
            t0 = time.time()
            return {"ts": t0}
        def measure(t0, t1):
            return t1 - t0
    """)
    assert check_obs_file(p) == []


def test_obs004_suppression_round_trip(tmp_path):
    src = """
        import time
        def align(anchor_ts):
            return time.time() - anchor_ts{supp}
    """
    fires = _write(str(tmp_path / "a.py"), src.format(supp=""))
    assert rules(apply_suppressions(check_obs_file(fires))) == ["OBS004"]
    silenced = _write(
        str(tmp_path / "b.py"),
        src.format(supp="  # analyze: ignore[OBS004]"),
    )
    assert apply_suppressions(check_obs_file(silenced)) == []


def test_obs004_real_tree_clean():
    found = apply_suppressions(check_obs(repo_root()))
    assert [f for f in found if f.rule == "OBS004"] == []


# -------------------------------------------------------- serving fixtures


def test_srv001_unbounded_queue_constructors(tmp_path):
    p = _write(str(tmp_path / "m.py"), """
        import queue
        class Server:
            def __init__(self):
                self._requests = queue.Queue()          # unbounded
                self._events = queue.SimpleQueue()      # always unbounded
                self._zero = queue.Queue(maxsize=0)     # 0 = unbounded too
    """)
    found = check_serving_file(p)
    assert rules(found) == ["SRV001"] * 3
    assert "OOM" in found[0].message


def test_srv001_silent_on_bounded_queues(tmp_path):
    p = _write(str(tmp_path / "m.py"), """
        import queue, os
        def make(depth):
            a = queue.Queue(maxsize=128)
            b = queue.Queue(64)
            c = queue.Queue(maxsize=depth)   # computed bound: trusted
            return a, b, c
    """)
    assert check_serving_file(p) == []


def test_srv001_blocking_get_and_wait_without_timeout(tmp_path):
    p = _write(str(tmp_path / "m.py"), """
        import queue, threading
        class Worker:
            def __init__(self):
                self._q = queue.Queue(maxsize=8)
                self._done = threading.Event()
            def run(self):
                item = self._q.get()        # blocks forever
                self._done.wait()           # blocks forever
                return item
    """)
    found = check_serving_file(p)
    assert rules(found) == ["SRV001"] * 2
    assert "timeout" in found[0].message


def test_srv001_silent_on_bounded_blocking_and_foreign_get(tmp_path):
    p = _write(str(tmp_path / "m.py"), """
        import os, queue, threading
        def run(config):
            q = queue.Queue(maxsize=8)
            ev = threading.Event()
            a = q.get(timeout=0.5)          # bounded
            b = q.get(False)                # non-blocking
            c = q.get(block=False)          # non-blocking
            d = q.get(True, 5)              # bounded positionally
            ev.wait(5)                      # bounded
            ev.wait(timeout=1.0)            # bounded
            # .get on receivers this module did NOT construct never fires
            e = config.get("key")
            f = os.environ.get("HOME")
            return a, b, c, d, e, f
    """)
    assert check_serving_file(p) == []


def test_srv001_tree_walker_only_visits_library_code(tmp_path):
    bad = "import queue\nq = queue.Queue()\n"
    _write(str(tmp_path / "mmlspark_tpu" / "m.py"), bad)
    _write(str(tmp_path / "tests" / "t.py"), bad)   # exempt by contract
    _write(str(tmp_path / "tools" / "u.py"), bad)   # exempt by contract
    assert rules(check_serving(str(tmp_path))) == ["SRV001"]


def test_srv001_suppression_round_trip(tmp_path):
    src = """
        import queue
        q = queue.Queue(){supp}
    """
    fires = _write(str(tmp_path / "a.py"), src.format(supp=""))
    assert rules(apply_suppressions(check_serving_file(fires))) == ["SRV001"]
    silenced = _write(str(tmp_path / "b.py"),
                      src.format(supp="  # analyze: ignore[SRV001]"))
    assert apply_suppressions(check_serving_file(silenced)) == []


def test_srv001_would_have_caught_the_seed_transport(tmp_path):
    """The literal pre-fix shape from io/http/serving.py: an unbounded
    request queue plus a reply-event wait with no timeout."""
    p = _write(str(tmp_path / "serving.py"), """
        import queue, threading
        class HTTPServer:
            def __init__(self):
                self._requests = queue.Queue()
                self._responders = {}
            def handle(self, rid):
                ev = threading.Event()
                self._responders[rid] = ev
                ev.wait()
                return self._responders.pop(rid)
    """)
    got = rules(check_serving_file(p))
    assert got == ["SRV001"] * 2


def test_srv002_popen_without_reap_path(tmp_path):
    p = _write(str(tmp_path / "m.py"), """
        import subprocess, sys
        class Fleet:
            def spawn(self):
                self._procs = [subprocess.Popen([sys.executable, "-m", "x"])]
            def stop(self):
                self._procs.clear()   # forgets the children entirely
    """)
    found = check_serving_file(p)
    assert rules(found) == ["SRV002"]
    assert "orphan" in found[0].message


def test_srv002_silent_with_reap_path_and_on_bounded_run(tmp_path):
    p = _write(str(tmp_path / "m.py"), """
        import subprocess, sys
        class Fleet:
            def spawn(self):
                self._proc = subprocess.Popen([sys.executable, "-m", "x"])
            def stop(self):
                self._proc.terminate()
                try:
                    self._proc.wait(timeout=30)
                except subprocess.TimeoutExpired:
                    self._proc.kill()
        def build():
            # run()/check_output block until the child exits: never fires
            subprocess.run(["make"], check=True)
            return subprocess.check_output(["git", "rev-parse", "HEAD"])
    """)
    assert check_serving_file(p) == []


def test_srv002_tree_walker_only_visits_library_code(tmp_path):
    bad = ("import subprocess\n"
           "p = subprocess.Popen(['sleep', '9'])\n")
    _write(str(tmp_path / "mmlspark_tpu" / "serve" / "m.py"), bad)
    _write(str(tmp_path / "tests" / "t.py"), bad)    # exempt by contract
    _write(str(tmp_path / "tools" / "u.py"), bad)    # exempt by contract
    assert rules(check_serving(str(tmp_path))) == ["SRV002"]


def test_srv002_suppression_round_trip(tmp_path):
    src = """
        import subprocess
        p = subprocess.Popen(["sleep", "9"]){supp}
    """
    fires = _write(str(tmp_path / "a.py"), src.format(supp=""))
    assert rules(apply_suppressions(check_serving_file(fires))) == ["SRV002"]
    silenced = _write(str(tmp_path / "b.py"),
                      src.format(supp="  # analyze: ignore[SRV002]"))
    assert apply_suppressions(check_serving_file(silenced)) == []


def test_srv002_real_router_is_clean():
    """The shipped FleetRouter spawns replicas AND carries the
    drain-or-kill path (stop(): SIGTERM -> bounded wait -> SIGKILL), so
    the real serve tree stays silent."""
    import mmlspark_tpu.serve.router as router_mod
    found = [f for f in check_serving_file(router_mod.__file__)
             if f.rule == "SRV002"]
    assert found == []


def test_loop001_looping_thread_without_join(tmp_path):
    p = _write(str(tmp_path / "m.py"), """
        import threading
        class Daemon:
            def start(self):
                t = threading.Thread(target=self._run, daemon=True)
                t.start()
            def _run(self):
                while True:
                    pass
    """)
    found = [f for f in check_serving_file(p) if f.rule == "LOOP001"]
    assert rules(found) == ["LOOP001"]
    assert "orphan" in found[0].message and "join" in found[0].message


def test_loop001_silent_with_stop_join_path(tmp_path):
    p = _write(str(tmp_path / "m.py"), """
        import threading
        class Daemon:
            def start(self):
                self._stop = threading.Event()
                self._t = threading.Thread(target=self._run, daemon=True)
                self._t.start()
            def _run(self):
                while not self._stop.is_set():
                    self._stop.wait(0.5)
            def stop(self):
                self._stop.set()
                self._t.join(timeout=5.0)
    """)
    assert [f for f in check_serving_file(p) if f.rule == "LOOP001"] == []


def test_loop001_silent_on_oneshot_and_foreign_targets(tmp_path):
    p = _write(str(tmp_path / "m.py"), """
        import threading
        def once(x):
            return x + 1
        def spawn(server):
            # one-shot worker: no while, bounded by construction
            threading.Thread(target=once, daemon=True).start()
            # imported/argument callable: not this module's to police
            threading.Thread(target=server.serve_forever).start()
            # lambdas/partials carry no resolvable name
            threading.Thread(target=lambda: None).start()
    """)
    assert [f for f in check_serving_file(p) if f.rule == "LOOP001"] == []


def test_loop001_suppression_round_trip(tmp_path):
    src = """
        import threading
        def _run():
            while True:
                pass
        t = threading.Thread(target=_run){supp}
    """
    fires = _write(str(tmp_path / "a.py"), src.format(supp=""))
    assert rules(apply_suppressions(check_serving_file(fires))) == [
        "LOOP001"]
    silenced = _write(str(tmp_path / "b.py"),
                      src.format(supp="  # analyze: ignore[LOOP001]"))
    assert apply_suppressions(check_serving_file(silenced)) == []


def test_loop001_real_loop_and_serve_modules_are_clean():
    """The shipped daemons (retrain controller, shadow replayer, quality
    monitor, serving workers) all carry the stop-flag + bounded-join
    teardown the rule demands, so the real tree stays silent."""
    import mmlspark_tpu.loop.controller as controller_mod
    import mmlspark_tpu.loop.shadow as shadow_mod
    import mmlspark_tpu.serve.app as app_mod
    import mmlspark_tpu.serve.monitor as monitor_mod
    for mod in (controller_mod, shadow_mod, app_mod, monitor_mod):
        found = [f for f in check_serving_file(mod.__file__)
                 if f.rule == "LOOP001"]
        assert found == [], mod.__name__


# ------------------------------------------------------------ suppressions


def test_suppression_round_trip(tmp_path):
    bad = """
        import jax
        def save(x):
            if jax.process_index() == 0:
                host_allgather(x){supp}
    """
    fires = _write(str(tmp_path / "a.py"), bad.format(supp=""))
    assert rules(apply_suppressions(check_collectives_file(fires))) == [
        "COL003"]

    silenced = _write(str(tmp_path / "b.py"),
                      bad.format(supp="  # analyze: ignore[COL003]"))
    assert apply_suppressions(check_collectives_file(silenced)) == []

    wrong_rule = _write(str(tmp_path / "c.py"),
                        bad.format(supp="  # analyze: ignore[COL001]"))
    assert rules(apply_suppressions(check_collectives_file(wrong_rule))) == [
        "COL003"]


def test_suppression_line_above_and_cpp_style(tmp_path):
    p = _write(str(tmp_path / "m.py"), """
        import jax
        def total(x):
            # analyze: ignore[COL001]
            return host_allgather(x) if jax.process_count() > 1 else x
    """)
    assert apply_suppressions(check_collectives_file(p)) == []

    cpp = _write(str(tmp_path / "k.cpp"), """
        extern "C" {
        void t(const double* row, int64_t* out) {
          const double x = row[0];
          // analyze: ignore[NAT001]
          out[0] = static_cast<int64_t>(x);
        }
        }
    """)
    assert apply_suppressions(check_float_casts(cpp)) == []


def test_unsuppressed_findings_pass_through(tmp_path):
    f = Finding(str(tmp_path / "nope.py"), 3, "COL001", "msg")
    assert apply_suppressions([f]) == [f]


# ------------------------------------- ADVICE r5 regression demonstrations


def test_advice_trace_cache_deadlock_would_be_caught(tmp_path):
    """ADVICE r5 medium: the literal pre-fix wrap_aot agreement helper —
    collective gated on process_count with no program-level evidence."""
    p = _write(str(tmp_path / "trace_cache.py"), """
        import numpy as np
        def _all_processes_ok(local_ok):
            import jax
            if jax.process_count() == 1:
                return local_ok
            from mmlspark_tpu.parallel.distributed import host_allgather
            flags = host_allgather(np.asarray([1 if local_ok else 0]))
            return bool(flags.reshape(-1).min())
    """)
    assert rules(check_collectives_file(p)) == ["COL001"]


def test_advice_c_long_bindings_would_be_caught(tmp_path):
    """ADVICE r5 low: the literal pre-fix _bind_binner ctypes block."""
    root = _abi_tree(
        tmp_path,
        cpp={"binner.cpp": """
            extern "C" {
            void mml_binner_fit(const double* Xs, long n, long F,
                                int max_bin, int min_data_in_bin,
                                const uint8_t* skip, double* out_uppers,
                                int* out_counts, int n_threads) {}
            }
        """},
        py={"__init__.py": """
            import ctypes
            def _bind_binner(lib):
                c_double_p = ctypes.POINTER(ctypes.c_double)
                c_int_p = ctypes.POINTER(ctypes.c_int)
                c_u8_p = ctypes.POINTER(ctypes.c_uint8)
                lib.mml_binner_fit.argtypes = [
                    c_double_p, ctypes.c_long, ctypes.c_long,
                    ctypes.c_int, ctypes.c_int, c_u8_p,
                    c_double_p, c_int_p, ctypes.c_int,
                ]
                lib.mml_binner_fit.restype = None
        """},
    )
    got = set(rules(check_abi(root)))
    # platform-width flagged on BOTH sides of the boundary
    assert {"ABI001", "ABI002"} <= got


def test_advice_clamp_divergence_would_be_caught(tmp_path):
    """ADVICE r5 low: the pre-fix transform_cat cast — a bare
    static_cast<int64_t> of an out-of-range-able double."""
    p = _write(str(tmp_path / "binner.cpp"), """
        extern "C" {
        void cat(const double* row, int64_t f, uint8_t* orow) {
          const double x = row[f];
          const int64_t v = static_cast<int64_t>(x);
          orow[f] = v > 0;
        }
        }
    """)
    assert rules(check_float_casts(p)) == ["NAT001"]


def test_advice_relatime_lru_would_be_caught(tmp_path):
    """ADVICE r5 low: the pre-fix jit_cache prune — atime-ordered LRU
    with no utime-on-hit anywhere in the module."""
    p = _write(str(tmp_path / "jit_cache.py"), """
        import os
        def prune_cache_dir(path, budget):
            entries = []
            with os.scandir(path) as it:
                for e in it:
                    if e.is_file():
                        st = e.stat()
                        entries.append(
                            (max(st.st_atime, st.st_mtime), st.st_size, e.path))
            total = sum(s for _, s, _ in entries)
            removed = 0
            for _, size, p in sorted(entries):
                if total <= budget:
                    break
                os.remove(p)
                removed += 1
                total -= size
            return removed
    """)
    assert rules(check_hygiene_file(p)) == ["HYG001"]


# ------------------------------------------------------------------- PRED001


def test_pred001_host_roundtrip_in_hot_path(tmp_path):
    p = _write(str(tmp_path / "m.py"), """
        import numpy as np
        class Booster:
            def predict(self, X):
                bins = np.asarray(self._score(X))       # device→host sync
                return np.ascontiguousarray(bins)
            def _raw_scores_binned(self, bins):
                return numpy.array(bins)
    """)
    found = check_predict_file(p)
    assert rules(found) == ["PRED001"] * 3
    assert "device" in found[0].message


def test_pred001_silent_outside_hot_paths(tmp_path):
    p = _write(str(tmp_path / "m.py"), """
        import numpy as np
        def fit(X):
            return np.asarray(X)          # training prep: host is fine
        def _build_table(vals):
            return np.ascontiguousarray(vals)
    """)
    assert check_predict_file(p) == []


def test_pred001_serve_batch_worker_is_hot(tmp_path):
    p = _write(str(tmp_path / "m.py"), """
        import numpy as np
        class Batcher:
            def _process(self, batch):
                return np.asarray(batch.preds)
    """)
    assert rules(check_predict_file(p)) == ["PRED001"]


def test_pred001_native_package_exempt(tmp_path):
    src = """
        import numpy as np
        def predict(model, X):
            return np.asarray(walk(model, X))
    """
    _write(str(tmp_path / "mmlspark_tpu" / "native" / "scorer.py"), src)
    fires = _write(str(tmp_path / "mmlspark_tpu" / "engine" / "b.py"), src)
    found = check_predict(str(tmp_path))
    assert rules(found) == ["PRED001"]
    assert found[0].file == fires


def test_pred001_suppression_marks_sanctioned_conversions(tmp_path):
    p = _write(str(tmp_path / "m.py"), """
        import numpy as np
        def predict(self, X):
            X = np.asarray(X, dtype=np.float64)  # analyze: ignore[PRED001]
            return self._score(X)
    """)
    assert apply_suppressions(check_predict_file(p)) == []


# ------------------------------------------------------------------- PRF001


def test_prf001_train_loop_over_models(tmp_path):
    p = _write(str(tmp_path / "fleet.py"), """
        def retrain_fleet(jobs):
            out = []
            for job in jobs:
                out.append(train(job.params, job.train_set))
            return out
        def stream_fleet(sources, params):
            models = []
            while sources:
                src = sources.pop()
                models.append(engine.train_streaming(params, src))
            return models
    """)
    found = check_perf_file(p)
    assert rules(found) == ["PRF001"] * 2
    assert "multi_train" in found[0].message


def test_prf001_silent_on_single_dispatch(tmp_path):
    p = _write(str(tmp_path / "ok.py"), """
        from mmlspark_tpu.engine.multi_train import MultiTrainJob, multi_train
        def retrain_fleet(jobs, mapper):
            mjobs = [MultiTrainJob(j.params, j.train_set) for j in jobs]
            return multi_train(mjobs, bin_mapper=mapper)
        def one_model(params, ds):
            for attempt in range(3):
                prepare(attempt)
            return train(params, ds)
    """)
    assert check_perf_file(p) == []


def test_prf001_suppression_round_trip(tmp_path):
    p = _write(str(tmp_path / "fallback.py"), """
        def refit_sequentially(jobs):
            for job in jobs:
                # deliberate degradation path when stacking is refused
                yield train(job.params, job.train_set)  # analyze: ignore[PRF001]
    """)
    raw = check_perf_file(p)
    assert rules(raw) == ["PRF001"]
    assert apply_suppressions(raw) == []


def test_prf001_scope_is_library_only(tmp_path):
    src = """
        def bench(jobs):
            for job in jobs:
                train(job.params, job.train_set)
    """
    _write(str(tmp_path / "tools" / "bench.py"), src)
    fires = _write(str(tmp_path / "mmlspark_tpu" / "loop" / "x.py"), src)
    found = check_perf(str(tmp_path))
    assert rules(found) == ["PRF001"]
    assert found[0].file == fires


# ------------------------------------------------------------------- CLI


def test_cli_exit_codes_and_json(tmp_path, capsys):
    import json as _json

    from tools.analyze import PASSES
    from tools.analyze.__main__ import main

    assert main([]) == 0  # the real tree is clean
    out = capsys.readouterr().out
    assert "0 finding(s)" in out

    assert main(["--json"]) == 0
    rep = _json.loads(capsys.readouterr().out)
    assert rep["findings"] == []
    # every pass (and the index build) reports its wall time
    assert set(PASSES) <= set(rep["timings"])
    assert "index_build" in rep["timings"]
    assert rep["total_s"] > 0

    # a dirty root exits 1 and reports file:line
    _write(str(tmp_path / "mmlspark_tpu" / "native" / "k.cpp"), """
        extern "C" {
        void f(long n);
        }
    """)
    _write(str(tmp_path / "mmlspark_tpu" / "__init__.py"), "")
    assert main(["--root", str(tmp_path)]) == 1
    out = capsys.readouterr().out
    assert "ABI001" in out and "k.cpp:3" in out


# ----------------------------------------------------- engine (ISSUE 7)
# The project index + the interprocedural passes.  Fixture trees are
# full mini-repos (root/mmlspark_tpu/...) because these rules only make
# sense across module boundaries.


def _pkg_tree(tmp_path, files):
    """root/mmlspark_tpu/<rel> for every (rel -> text), with package
    __init__.py files auto-created."""
    root = str(tmp_path)
    pkg = os.path.join(root, "mmlspark_tpu")
    _write(os.path.join(pkg, "__init__.py"), "")
    for rel, text in files.items():
        path = os.path.join(pkg, rel)
        _write(path, text)
        d = os.path.dirname(path)
        while len(d) > len(pkg):
            init = os.path.join(d, "__init__.py")
            if not os.path.exists(init):
                _write(init, "")
            d = os.path.dirname(d)
    return root


def test_engine_index_resolves_cross_module_calls(tmp_path):
    from tools.analyze.engine import build_index

    root = _pkg_tree(tmp_path, {
        "a.py": """
            from mmlspark_tpu.b import helper

            def top():
                return helper()
        """,
        "b.py": """
            def helper():
                return 1
        """,
    })
    index = build_index(root)
    fi = index.modules["mmlspark_tpu.a"].defs["top"]
    (site,) = fi.calls
    assert site.callee is index.modules["mmlspark_tpu.b"].defs["helper"]


def test_engine_index_attr_alias_and_guard_context(tmp_path):
    from tools.analyze.engine import build_index

    root = _pkg_tree(tmp_path, {
        "serve/app.py": """
            class App:
                def __init__(self, server):
                    server.intake = self._intake

                def _intake(self, rid):
                    if rid > 0:
                        self._dispatch(rid)

                def _dispatch(self, rid):
                    pass
        """,
    })
    index = build_index(root)
    app = index.modules["mmlspark_tpu.serve.app"].classes["App"]
    # the attribute assignment aliases intake -> App._intake
    (alias,) = index.attr_aliases["intake"]
    assert alias is app.methods["_intake"]
    # the call site inside the if carries its guard
    (site,) = app.methods["_intake"].calls
    assert site.callee is app.methods["_dispatch"]
    assert site.guards == ("rid > 0",)


# -------------------------------------------------- COL005/COL006 fixtures


_DIVERGENT_BOOSTER = """
    import jax
    from mmlspark_tpu.parallel.helpers import merge_stats

    def train(params, data):
        stats = data
        if jax.process_index() == 0:
            stats = merge_stats(stats)
        return stats
"""
_DIVERGENT_HELPERS = """
    from mmlspark_tpu.parallel.distributed import device_psum

    def merge_stats(x):
        return device_psum(x, "data")
"""
_FIXTURE_DISTRIBUTED = """
    def device_psum(x, axis):
        return x
"""


def test_col005_cross_module_divergent_collective(tmp_path):
    """The headline regression: a rank-pinned edge in booster reaches a
    collective defined in ANOTHER module.  The interprocedural engine
    flags it; the per-file engine provably cannot (neither half alone
    contains both the guard and the collective)."""
    root = _pkg_tree(tmp_path, {
        "engine/booster.py": _DIVERGENT_BOOSTER,
        "parallel/helpers.py": _DIVERGENT_HELPERS,
        "parallel/distributed.py": _FIXTURE_DISTRIBUTED,
    })
    found = run_all(root, rules={"COL005"})
    assert rules(found) == ["COL005"]
    assert "rank-gated edge" in found[0].message
    assert found[0].file.endswith(os.path.join("engine", "booster.py"))

    # file-by-file, the same two halves are silent: the guard's file has
    # no collective and the collective's file has no guard
    for rel in ("engine/booster.py", "parallel/helpers.py"):
        path = os.path.join(root, "mmlspark_tpu", *rel.split("/"))
        assert check_collectives_file(path) == [], rel


def test_col005_silent_with_all_ranks_evidence(tmp_path):
    root = _pkg_tree(tmp_path, {
        "engine/booster.py": """
            import jax
            from mmlspark_tpu.parallel.helpers import merge_stats

            def train(params, data, mesh_spans_processes):
                if jax.process_count() > 1 and mesh_spans_processes:
                    data = merge_stats(data)
                return data
        """,
        "parallel/helpers.py": _DIVERGENT_HELPERS,
        "parallel/distributed.py": _FIXTURE_DISTRIBUTED,
    })
    assert run_all(root, rules={"COL005"}) == []


def test_col006_rank_local_loop_trip_count(tmp_path):
    root = _pkg_tree(tmp_path, {
        "parallel/helpers.py": """
            from mmlspark_tpu.parallel.distributed import device_psum

            def drain(local_parts):
                out = []
                for part in local_parts:
                    out.append(device_psum(part, "data"))
                return out
        """,
        "parallel/distributed.py": _FIXTURE_DISTRIBUTED,
    })
    found = run_all(root, rules={"COL006"})
    assert rules(found) == ["COL006"]
    assert "trip count" in found[0].message


def test_col006_silent_on_globally_agreed_loop(tmp_path):
    root = _pkg_tree(tmp_path, {
        "engine/booster.py": """
            from mmlspark_tpu.parallel.distributed import device_psum

            def train(params, data):
                for it in range(params["num_iterations"]):
                    data = device_psum(data, "data")
                return data
        """,
        "parallel/distributed.py": _FIXTURE_DISTRIBUTED,
    })
    assert run_all(root, rules={"COL005", "COL006"}) == []


# ------------------------------------------------------- LCK fixtures


def test_lck001_lock_held_across_nested_acquire(tmp_path):
    root = _pkg_tree(tmp_path, {
        "serve/reg.py": """
            import threading

            class Version:
                def __init__(self):
                    self._vlock = threading.Lock()
                    self.refs = 0

                def acquire(self):
                    with self._vlock:
                        self.refs += 1

            class Registry:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._routes = {}

                def lease(self, name):
                    with self._lock:
                        mv = self._routes[name]
                        mv.acquire()
                    return mv
        """,
    })
    found = run_all(root, rules={"LCK001"})
    assert rules(found) == ["LCK001"]
    assert "Version._vlock" in found[0].message


def test_lck001_silent_when_acquire_moves_outside(tmp_path):
    root = _pkg_tree(tmp_path, {
        "serve/reg.py": """
            import threading

            class Version:
                def __init__(self):
                    self._vlock = threading.Lock()
                    self.refs = 0

                def acquire(self):
                    with self._vlock:
                        self.refs += 1

            class Registry:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._routes = {}

                def lease(self, name):
                    with self._lock:
                        mv = self._routes[name]
                    mv.acquire()
                    return mv
        """,
    })
    assert run_all(root, rules={"LCK001"}) == []


def test_lck002_blocking_get_under_lock(tmp_path):
    root = _pkg_tree(tmp_path, {
        "serve/pump.py": """
            import queue
            import threading

            class Pump:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._q = queue.Queue(maxsize=8)

                def pull(self):
                    with self._lock:
                        item = self._q.get(timeout=1.0)
                    return item
        """,
    })
    found = run_all(root, rules={"LCK002"})
    assert rules(found) == ["LCK002"]


def test_lck002_silent_on_nonblocking_forms(tmp_path):
    root = _pkg_tree(tmp_path, {
        "serve/pump.py": """
            import queue
            import threading

            class Pump:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._q = queue.Queue(maxsize=8)

                def push(self, item):
                    with self._lock:
                        self._q.put_nowait(item)

                def try_pull(self):
                    with self._lock:
                        return self._q.get(block=False)

                def pull(self):
                    item = self._q.get(timeout=1.0)
                    with self._lock:
                        pass
                    return item
        """,
    })
    assert run_all(root, rules={"LCK002"}) == []


_LCK003_APP = """
    import threading
    from http.server import BaseHTTPRequestHandler

    class App:
        def __init__(self):
            self.total = 0
            self._t = threading.Thread(target=self._worker)

        def _worker(self):
            self.total = self.total + 1

        def _handle_request(self, rid):
            return self.total

    class Handler(BaseHTTPRequestHandler):
        def do_POST(self):
            self.app._handle_request("r1")
"""


def test_lck003_cross_thread_domain_write(tmp_path):
    root = _pkg_tree(tmp_path, {"serve/app.py": _LCK003_APP})
    found = run_all(root, rules={"LCK003"})
    assert rules(found) == ["LCK003"]
    assert "self.total" in found[0].message
    assert "worker" in found[0].message and "request" in found[0].message


def test_lck003_silent_under_common_lock(tmp_path):
    root = _pkg_tree(tmp_path, {
        "serve/app.py": """
            import threading
            from http.server import BaseHTTPRequestHandler

            class App:
                def __init__(self):
                    self._lock = threading.Lock()
                    self.total = 0
                    self._t = threading.Thread(target=self._worker)

                def _worker(self):
                    with self._lock:
                        self.total = self.total + 1

                def _handle_request(self, rid):
                    with self._lock:
                        return self.total

            class Handler(BaseHTTPRequestHandler):
                def do_POST(self):
                    self.app._handle_request("r1")
        """,
    })
    assert run_all(root, rules={"LCK003"}) == []


# ------------------------------------------------------- DTY001 fixtures


def test_dty001_direct_f32_narrowing(tmp_path):
    root = _pkg_tree(tmp_path, {
        "ops/device_binning.py": """
            import numpy as np

            def bad_pack(bm):
                table = np.asarray(bm.upper_bounds[0], np.float64)
                return table.astype(np.float32)
        """,
    })
    found = run_all(root, rules={"DTY001"})
    assert rules(found) == ["DTY001"]
    assert "double-single" in found[0].message


def test_dty001_sanctioned_double_single_is_silent(tmp_path):
    root = _pkg_tree(tmp_path, {
        "ops/device_binning.py": """
            import numpy as np

            def good_pack(bm):
                table = np.asarray(bm.upper_bounds[0], np.float64)
                hi = table.astype(np.float32)
                lo = np.zeros_like(table)
                np.subtract(table, hi.astype(np.float64), out=lo)
                lo = lo.astype(np.float32)
                return hi, lo
        """,
    })
    assert run_all(root, rules={"DTY001"}) == []


def test_dty001_interprocedural_flow_into_helper(tmp_path):
    root = _pkg_tree(tmp_path, {
        "engine/booster.py": """
            import numpy as np

            def _narrow(edges):
                return np.asarray(edges, dtype=np.float32)

            def _fit(params, bm):
                edges = bm.upper_bounds[0]
                return _narrow(edges)
        """,
    })
    found = run_all(root, rules={"DTY001"})
    assert rules(found) == ["DTY001"]
    assert found[0].file.endswith("booster.py")


def test_dty001_index_valued_results_drop_taint(tmp_path):
    root = _pkg_tree(tmp_path, {
        "ops/binning.py": """
            import numpy as np

            def assign_bins(bm, col):
                bins = np.searchsorted(bm.upper_bounds[0], col)
                return bins.astype(np.float32)
        """,
    })
    assert run_all(root, rules={"DTY001"}) == []


# ------------------------------------------------------- QNT001 fixtures


def test_qnt001_unattested_int_accumulator(tmp_path):
    # the seeded bug: an int32 histogram accumulator with no headroom
    # note — n·QMAX overflow would wrap silently
    p = _write(str(tmp_path / "hist.py"), """
        import jax.numpy as jnp
        def build_hist(bins, vals, F, B):
            acc = jnp.zeros((3, F, B), jnp.int32)
            return acc.at[..., bins].add(vals)
    """)
    assert rules(check_quantize_file(p)) == ["QNT001"]


def test_qnt001_fires_by_function_name_outside_hist_file(tmp_path):
    # file name is neutral; the enclosing function is histogram code
    p = _write(str(tmp_path / "m.py"), """
        import jax.numpy as jnp
        def _scatter_hist_chunk_int(idx, vals, F, B):
            return jnp.zeros(F * B, jnp.int16).at[idx].add(vals)
    """)
    assert rules(check_quantize_file(p)) == ["QNT001"]


def test_qnt001_matmul_accumulator_and_out_shape(tmp_path):
    # the Pallas shapes: int32 ShapeDtypeStruct grid accumulator and an
    # integer preferred_element_type contraction
    p = _write(str(tmp_path / "pallas_hist.py"), """
        import jax
        import jax.numpy as jnp
        def _pallas_hist_int(F, B):
            return jax.ShapeDtypeStruct((3, F, B), jnp.int32)
        def _hist_kernel_int(oh, vals):
            return jax.lax.dot_general(
                oh, vals, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.int32)
    """)
    assert rules(check_quantize_file(p)) == ["QNT001", "QNT001"]


def test_qnt001_silent_with_headroom_attestation(tmp_path):
    p = _write(str(tmp_path / "hist.py"), """
        import jax.numpy as jnp
        def build_hist(bins, vals, F, B):
            # headroom: n*QMAX bin sums fit int32 (quantize_wire_plan)
            acc = jnp.zeros((3, F, B), jnp.int32)
            return acc.at[..., bins].add(vals)
    """)
    assert check_quantize_file(p) == []


def test_qnt001_silent_outside_hist_context(tmp_path):
    # int32 index/packing arrays in non-histogram code are not
    # accumulators — the forest node table, bin ids, argsort ranks
    p = _write(str(tmp_path / "forest.py"), """
        import jax.numpy as jnp
        def pack_nodes(n):
            return jnp.zeros((n, 4), jnp.int32)
    """)
    assert check_quantize_file(p) == []


def test_qnt001_silent_on_float_accumulators(tmp_path):
    p = _write(str(tmp_path / "hist.py"), """
        import jax.numpy as jnp
        def build_hist(bins, vals, F, B):
            bin_ids = jnp.zeros(F, jnp.int8)  # not a 16/32-bit accumulator
            return jnp.zeros((3, F, B), jnp.float32).at[..., bins].add(vals)
    """)
    assert check_quantize_file(p) == []


def test_qnt001_suppression_roundtrip(tmp_path):
    # a site whose bound lives elsewhere suppresses inline; the stale
    # checker still sees the raw finding under the comment
    p = _write(str(tmp_path / "hist.py"), """
        import jax.numpy as jnp
        def build_hist(bins, vals, F, B):
            acc = jnp.zeros((3, F, B), jnp.int32)  # analyze: ignore[QNT001]
            return acc.at[..., bins].add(vals)
    """)
    raw = check_quantize_file(p)
    assert rules(raw) == ["QNT001"]
    assert apply_suppressions(raw) == []


def test_qnt001_library_int_accumulators_are_attested():
    # every int16/int32 accumulator the quantized path ships (histogram.py
    # chunk builders, pallas_hist.py int kernels) carries its headroom note
    from tools.analyze.quantize_rules import check_quantize

    assert apply_suppressions(check_quantize(repo_root())) == []


# ------------------------------------------------------------------- ING001


def test_ing001_full_materialization_in_data_module(tmp_path):
    from tools.analyze.ingest_rules import check_ingest_file

    p = _write(str(tmp_path / "data" / "m.py"), """
        import numpy as np
        def read_shard(p):
            X = np.load(p)                  # eager: whole shard in RAM
            X = np.asarray(X, np.float32)   # whole-frame copy
            X = X.astype(np.float64)        # and again
            return X
        def fit_edges(binner, X):
            return binner.fit(X)            # host full-data pass
    """)
    found = check_ingest_file(p)
    assert rules(found) == ["ING001"] * 4
    assert "O(chunk)" in found[0].message


def test_ing001_chunked_code_is_silent(tmp_path):
    from tools.analyze.ingest_rules import check_ingest_file

    p = _write(str(tmp_path / "data" / "m.py"), """
        import numpy as np
        def read_shard(p):
            X = np.load(p, mmap_mode="r")          # lazy: fine
            for start in range(0, len(X), 4096):
                X_chunk = np.asarray(X[start:start + 4096])
                yield X_chunk.astype(np.float32)   # chunk-shaped: fine
    """)
    assert check_ingest_file(p) == []


def test_ing001_scoped_to_data_and_stream_fns(tmp_path):
    from tools.analyze.ingest_rules import check_ingest_file

    p = _write(str(tmp_path / "engine" / "m.py"), """
        import numpy as np
        def fit(X):
            return np.asarray(X)        # host training prep: out of scope
        def stream_fit(src, X):
            return np.asarray(X)        # streaming hot path: in scope
        def chunk_ingest(X):
            return X.astype(np.float32)  # ingest hot path: in scope
    """)
    assert rules(check_ingest_file(p)) == ["ING001"] * 2


def test_ing001_suppression_roundtrip(tmp_path):
    from tools.analyze.ingest_rules import check_ingest_file

    p = _write(str(tmp_path / "data" / "m.py"), """
        import numpy as np
        def _write_fixture(path, X):
            X = np.asarray(X, np.float32)  # analyze: ignore[ING001]
            X.tofile(path)
    """)
    raw = check_ingest_file(p)
    assert rules(raw) == ["ING001"]
    assert apply_suppressions(raw) == []


def test_ing001_real_data_plane_is_clean():
    # the shipped ingest pipeline (data/loader.py, data/streaming.py,
    # data/sketch.py) holds its own O(chunk) contract; the two fixture-
    # writer conversions in write_row_group_shards are the only
    # sanctioned sites
    from tools.analyze.ingest_rules import check_ingest

    assert apply_suppressions(check_ingest(repo_root())) == []


def test_ing001_glob_and_index_walks_agree():
    from tools.analyze.engine import build_index
    from tools.analyze.ingest_rules import check_ingest

    root = repo_root()
    key = lambda f: (f.file, f.line, f.rule, f.message)
    legacy = sorted(map(key, check_ingest(root)))
    indexed = sorted(map(key, check_ingest(root, index=build_index(root))))
    assert legacy == indexed


# ------------------------------------------------- golden + parity gates


def test_engine_port_golden_parity_on_real_tree():
    """All seven pre-existing passes produce the SAME findings through
    the index as through the legacy per-file glob walk."""
    from tools.analyze import (
        check_abi, check_collectives, check_hygiene, check_obs,
        check_predict, check_serving, check_tracer,
    )
    from tools.analyze.engine import build_index

    root = repo_root()
    index = build_index(root)
    key = lambda f: (f.file, f.line, f.rule, f.message)
    for chk in (check_abi, check_collectives, check_tracer,
                check_hygiene, check_obs, check_serving, check_predict):
        legacy = sorted(map(key, chk(root)))
        indexed = sorted(map(key, chk(root, index=index)))
        assert legacy == indexed, chk.__name__


# ------------------------------------------- suppression edge cases


def test_suppression_multi_rule_single_comment(tmp_path):
    p = _write(str(tmp_path / "x.py"),
               "risky()  # analyze: ignore[AAA001,BBB002]\n")
    findings = [Finding(p, 1, "AAA001", "m"), Finding(p, 1, "BBB002", "m"),
                Finding(p, 1, "CCC003", "m")]
    assert rules(apply_suppressions(findings)) == ["CCC003"]


def test_suppression_on_decorator_line_covers_def(tmp_path):
    p = _write(str(tmp_path / "x.py"), """
        @decorator  # analyze: ignore[XYZ001]
        @other
        def f():
            pass
    """)
    # covers the comment line, subsequent decorators, the def line, and
    # the line after the def
    covered = [Finding(p, n, "XYZ001", "m") for n in (2, 3, 4, 5)]
    assert apply_suppressions(covered) == []
    # ...but not further into the body, and not other rules
    kept = [Finding(p, 6, "XYZ001", "m"), Finding(p, 4, "OTHER1", "m")]
    assert len(apply_suppressions(kept)) == 2


def test_stale_ignores_report(tmp_path):
    from tools.analyze import run_stale_ignores

    root = _pkg_tree(tmp_path, {
        "a.py": "x = 1  # analyze: ignore[OBS001]\n",
        "b.py": 'print("hi")  # analyze: ignore[OBS001]\n',
    })
    stale = run_stale_ignores(root)
    assert [f.rule for f in stale] == ["STALE"]
    assert stale[0].file.endswith("a.py")
    assert "ignore[OBS001]" in stale[0].message


def test_real_tree_has_no_stale_ignores():
    from tools.analyze import run_stale_ignores

    stale = run_stale_ignores(repo_root())
    assert stale == [], "\n".join(str(f) for f in stale)


# ----------------------------------------------------- CLI (ISSUE 7)


def _dirty_root(tmp_path):
    _write(str(tmp_path / "mmlspark_tpu" / "native" / "k.cpp"), """
        extern "C" {
        void f(long n);
        }
    """)
    _write(str(tmp_path / "mmlspark_tpu" / "__init__.py"), "")
    _write(str(tmp_path / "mmlspark_tpu" / "core" / "__init__.py"), "")
    _write(str(tmp_path / "mmlspark_tpu" / "core" / "x.py"),
           'print("noisy")\n')
    return str(tmp_path)


def test_cli_sarif_output(tmp_path, capsys):
    import json as _json

    from tools.analyze.__main__ import main

    root = _dirty_root(tmp_path)
    assert main(["--root", root, "--sarif"]) == 1
    doc = _json.loads(capsys.readouterr().out)
    assert doc["version"] == "2.1.0"
    results = doc["runs"][0]["results"]
    assert {r["ruleId"] for r in results} == {"ABI001", "OBS001"}
    abi = next(r for r in results if r["ruleId"] == "ABI001")
    loc = abi["locations"][0]["physicalLocation"]
    assert loc["artifactLocation"]["uri"] == "mmlspark_tpu/native/k.cpp"
    assert loc["region"]["startLine"] == 3
    rule_ids = {r["id"] for r in doc["runs"][0]["tool"]["driver"]["rules"]}
    assert rule_ids == {"ABI001", "OBS001"}


def test_cli_rule_and_path_filters(tmp_path, capsys):
    from tools.analyze.__main__ import main

    root = _dirty_root(tmp_path)
    assert main(["--root", root, "--rule", "OBS001"]) == 1
    out = capsys.readouterr().out
    assert "OBS001" in out and "ABI001" not in out

    assert main(["--root", root, "--path", "mmlspark_tpu/native"]) == 1
    out = capsys.readouterr().out
    assert "ABI001" in out and "OBS001" not in out

    assert main(["--root", root, "--path", "mmlspark_tpu/serve"]) == 0

    with pytest.raises(SystemExit):  # unknown rule id is an arg error
        main(["--root", root, "--rule", "NOPE999"])


def test_cli_stale_ignores_exit_codes(tmp_path, capsys):
    from tools.analyze.__main__ import main

    root = _pkg_tree(tmp_path, {
        "a.py": "x = 1  # analyze: ignore[OBS001]\n",
    })
    assert main(["--root", root, "--stale-ignores"]) == 1
    out = capsys.readouterr().out
    assert "STALE" in out and "stale ignore(s)" in out


def test_cli_internal_error_exits_2(tmp_path, capsys, monkeypatch):
    import tools.analyze as pkg
    from tools.analyze.__main__ import main

    def boom(*a, **k):
        raise RuntimeError("seeded internal failure")

    monkeypatch.setattr(pkg, "run_all", boom)
    assert main([]) == 2
    assert "internal error" in capsys.readouterr().err


# ----------------------------------------- DET001..DET004 (determinism)
# Taint flow from nondeterministic-order sources (unsorted directory
# scans, set iteration, wall clock) into order/key-sensitive sinks
# (collective wrappers, digests, manifests, fingerprints), plus the
# syntactic global-RNG sweep.


def test_det001_unsorted_scan_reaches_digest(tmp_path):
    root = _pkg_tree(tmp_path, {
        "parallel/manifest.py": """
            import hashlib
            import os

            def shard_digest(d):
                h = hashlib.sha256()
                for fn in os.listdir(d):
                    h.update(fn.encode())
                return h.hexdigest()
        """,
    })
    found = run_all(root, rules={"DET001"})
    assert rules(found) == ["DET001"]
    assert "filesystem-scan" in found[0].message


def test_det001_interprocedural_hop_through_helper(tmp_path):
    root = _pkg_tree(tmp_path, {
        "parallel/manifest.py": """
            import glob
            import hashlib
            import os

            def _collect(paths):
                return list(paths)

            def digest_dir(d):
                names = glob.glob(os.path.join(d, "*.bin"))
                rows = _collect(names)
                h = hashlib.sha256()
                for r in rows:
                    h.update(r.encode())
                return h.hexdigest()
        """,
    })
    found = run_all(root, rules={"DET001"})
    assert rules(found) == ["DET001"]


def test_det001_sorted_scan_is_silent(tmp_path):
    root = _pkg_tree(tmp_path, {
        "parallel/manifest.py": """
            import hashlib
            import os

            def shard_digest(d):
                h = hashlib.sha256()
                for fn in sorted(os.listdir(d)):
                    h.update(fn.encode())
                return h.hexdigest()
        """,
    })
    assert run_all(root, rules={"DET001"}) == []


def test_det001_suppression_round_trip(tmp_path):
    root = _pkg_tree(tmp_path, {
        "parallel/manifest.py": """
            import hashlib
            import os

            def shard_digest(d):
                h = hashlib.sha256()
                for fn in os.listdir(d):
                    h.update(fn.encode())  # analyze: ignore[DET001]
                return h.hexdigest()
        """,
    })
    assert run_all(root, rules={"DET001"}) == []
    raw = run_all(root, rules={"DET001"}, suppress=False)
    assert rules(raw) == ["DET001"]


def test_det002_set_order_reaches_collective(tmp_path):
    root = _pkg_tree(tmp_path, {
        "parallel/gather.py": """
            def gather_feats(feats, x, host_allgather):
                chosen = {f for f in feats if f > 0}
                payload = [x[i] for i in chosen]
                return host_allgather(payload)
        """,
    })
    found = run_all(root, rules={"DET002"})
    assert rules(found) == ["DET002"]
    assert "set-iteration" in found[0].message


def test_det002_sorted_set_is_silent(tmp_path):
    root = _pkg_tree(tmp_path, {
        "parallel/gather.py": """
            def gather_feats(feats, x, host_allgather):
                chosen = {f for f in feats if f > 0}
                payload = [x[i] for i in sorted(chosen)]
                return host_allgather(payload)
        """,
    })
    assert run_all(root, rules={"DET002"}) == []


def test_det002_jax_functional_set_update_is_silent(tmp_path):
    # jax's `votes.at[idx].set(1.0)` has call leaf "set" — it must NOT
    # count as a set-iteration source (the pre-fix false positive that
    # flagged every voting psum in engine/tree.py)
    root = _pkg_tree(tmp_path, {
        "engine/vote.py": """
            from jax import lax

            def tally(votes, idx, axis_name):
                votes = votes.at[idx].set(1.0)
                return lax.psum(votes, axis_name)
        """,
    })
    assert run_all(root, rules={"DET002"}) == []


def test_det002_suppression_round_trip(tmp_path):
    root = _pkg_tree(tmp_path, {
        "parallel/gather.py": """
            def gather_feats(feats, x, host_allgather):
                chosen = {f for f in feats if f > 0}
                # analyze: ignore[DET002]
                return host_allgather(list(chosen))
        """,
    })
    assert run_all(root, rules={"DET002"}) == []
    assert rules(run_all(root, rules={"DET002"},
                         suppress=False)) == ["DET002"]


def test_det003_global_rng_calls_fire(tmp_path):
    root = _pkg_tree(tmp_path, {
        "data/sample.py": """
            import random

            import numpy as np

            def shuffle_rows(x):
                idx = np.random.permutation(len(x))
                random.shuffle(idx)
                rng = np.random.default_rng()
                return x[idx], rng
        """,
    })
    found = run_all(root, rules={"DET003"})
    assert rules(found) == ["DET003", "DET003", "DET003"]


def test_det003_seeded_and_local_generators_silent(tmp_path):
    root = _pkg_tree(tmp_path, {
        "data/sample.py": """
            import numpy as np

            def shuffle_rows(x, seed):
                rng = np.random.default_rng(seed)
                other = np.random.default_rng(0)
                rng.shuffle(x)
                return x, other
        """,
    })
    assert run_all(root, rules={"DET003"}) == []


def test_det003_suppression_round_trip(tmp_path):
    root = _pkg_tree(tmp_path, {
        "data/sample.py": """
            import numpy as np

            def jitter(x):
                return x + np.random.normal()  # analyze: ignore[DET003]
        """,
    })
    assert run_all(root, rules={"DET003"}) == []
    assert rules(run_all(root, rules={"DET003"},
                         suppress=False)) == ["DET003"]


def test_det004_wall_clock_reaches_fingerprint(tmp_path):
    root = _pkg_tree(tmp_path, {
        "core/keys.py": """
            import hashlib
            import time

            def cache_key(name):
                stamp = time.time()
                return hashlib.md5(f"{name}:{stamp}".encode()).hexdigest()
        """,
    })
    found = run_all(root, rules={"DET004"})
    assert rules(found) == ["DET004"]
    assert "wall-clock" in found[0].message


def test_det004_datetime_now_into_cache_subscript(tmp_path):
    root = _pkg_tree(tmp_path, {
        "core/keys.py": """
            import datetime

            _CACHE = {}

            def remember(name, value):
                stamp = datetime.datetime.now().isoformat()
                _CACHE[f"{name}:{stamp}"] = value
        """,
    })
    found = run_all(root, rules={"DET004"})
    assert rules(found) == ["DET004"]


def test_det004_duration_logging_is_silent(tmp_path):
    root = _pkg_tree(tmp_path, {
        "core/keys.py": """
            import time

            def timed(fn):
                t0 = time.monotonic()
                out = fn()
                print(time.monotonic() - t0)
                return out
        """,
    })
    assert run_all(root, rules={"DET004"}) == []


def test_det004_suppression_round_trip(tmp_path):
    root = _pkg_tree(tmp_path, {
        "core/keys.py": """
            import hashlib
            import time

            def cache_key(name):
                stamp = time.time()
                # analyze: ignore[DET004]
                return hashlib.md5(f"{name}:{stamp}".encode()).hexdigest()
        """,
    })
    assert run_all(root, rules={"DET004"}) == []
    assert rules(run_all(root, rules={"DET004"},
                         suppress=False)) == ["DET004"]


def test_det_real_tree_is_clean():
    """Regression pin for the live fixes: every manifest/digest path in
    the real tree scans sorted and no wall clock reaches a cache key."""
    assert run_all(repo_root(),
                   rules={"DET001", "DET002", "DET003", "DET004"}) == []


# ------------------------------------------ DON001/DON002 (donation)
# Use-after-donation returns garbage on TPU but works on CPU (the
# buffer is only really invalidated on accelerators), so tests never
# catch it — the analyzer has to.


def test_don001_read_after_donation_module_binding(tmp_path):
    root = _pkg_tree(tmp_path, {
        "data/cache.py": """
            import jax

            def _step(buf, occ, rows):
                return buf + rows, occ + 1

            step = jax.jit(_step, donate_argnums=(0, 1))

            def bad_loop(buf, occ, rows):
                out, occ2 = step(buf, occ, rows)
                total = buf.sum()
                return out, occ2, total
        """,
    })
    found = run_all(root, rules={"DON001"})
    assert rules(found) == ["DON001"]
    assert "donated" in found[0].message
    assert "'buf'" in found[0].message


def test_don001_local_binding_and_any_path_read(tmp_path):
    # the read only happens on ONE CFG path — must still fire
    root = _pkg_tree(tmp_path, {
        "data/cache.py": """
            import jax

            def _step(buf, occ):
                return buf * 2, occ + 1

            def run(buf, occ, check):
                step = jax.jit(_step, donate_argnums=(0,))
                out, occ = step(buf, occ)
                if check:
                    return buf.sum()
                return out
        """,
    })
    found = run_all(root, rules={"DON001"})
    assert rules(found) == ["DON001"]


def test_don001_rebinding_idiom_is_silent(tmp_path):
    # the data/streaming.py shape: the donated operand is REBOUND by the
    # call's own result, so no stale name survives the call
    root = _pkg_tree(tmp_path, {
        "data/cache.py": """
            import jax

            def _step(buf, occ, rows):
                return buf + rows, occ + 1

            step = jax.jit(_step, donate_argnums=(0, 1))

            def good_loop(buf, occ, chunks):
                for rows in chunks:
                    buf, occ = step(buf, occ, rows)
                buf.block_until_ready()
                return buf, occ
        """,
    })
    assert run_all(root, rules={"DON001"}) == []


def test_don001_suppression_round_trip(tmp_path):
    root = _pkg_tree(tmp_path, {
        "data/cache.py": """
            import jax

            def _step(buf):
                return buf * 2

            step = jax.jit(_step, donate_argnums=(0,))

            def peek(buf):
                out = step(buf)
                return out, buf.shape  # analyze: ignore[DON001]
        """,
    })
    assert run_all(root, rules={"DON001"}) == []
    assert rules(run_all(root, rules={"DON001"},
                         suppress=False)) == ["DON001"]


def test_don002_aliased_donated_arguments(tmp_path):
    root = _pkg_tree(tmp_path, {
        "data/cache.py": """
            import jax

            def _step(a, b):
                return a + b

            step = jax.jit(_step, donate_argnums=(0, 1))

            def aliased(buf):
                other = buf
                return step(buf, other)
        """,
    })
    found = run_all(root, rules={"DON002"})
    assert rules(found) == ["DON002"]
    assert "alias" in found[0].message


def test_don002_distinct_buffers_silent(tmp_path):
    root = _pkg_tree(tmp_path, {
        "data/cache.py": """
            import jax

            def _step(a, b):
                return a + b

            step = jax.jit(_step, donate_argnums=(0, 1))

            def fine(buf, occ):
                return step(buf, occ)
        """,
    })
    assert run_all(root, rules={"DON002"}) == []


def test_don002_suppression_round_trip(tmp_path):
    root = _pkg_tree(tmp_path, {
        "data/cache.py": """
            import jax

            def _step(a, b):
                return a + b

            step = jax.jit(_step, donate_argnums=(0, 1))

            def aliased(buf):
                other = buf
                return step(buf, other)  # analyze: ignore[DON002]
        """,
    })
    assert run_all(root, rules={"DON002"}) == []
    assert rules(run_all(root, rules={"DON002"},
                         suppress=False)) == ["DON002"]


def test_don_real_tree_is_clean():
    """Regression pin: the live donation sites (data/streaming.py's
    donated chunk loop above all) use the rebinding idiom and never
    touch a stale donated name."""
    assert run_all(repo_root(), rules={"DON001", "DON002"}) == []


# -------------------------------------------------- per-pass timings


def test_full_run_attributes_its_wall_to_fifteen_passes():
    """All fifteen passes run (index built once), and the timings
    out-param attributes the wall per pass."""
    from tools.analyze import PASSES

    assert len(PASSES) == 15
    timings = {}
    run_all(repo_root(), timings=timings)
    assert set(PASSES) <= set(timings)
    assert "index_build" in timings
    assert all(v >= 0 for v in timings.values())


# ------------------------------------------------- --changed-only


def _git(root, *args):
    import subprocess

    return subprocess.run(
        ["git", "-C", root, "-c", "user.email=ci@example.invalid",
         "-c", "user.name=ci", *args],
        check=True, capture_output=True, text=True).stdout


def test_cli_changed_only_filters_to_diff(tmp_path, capsys):
    from tools.analyze.__main__ import main

    root = _pkg_tree(tmp_path, {
        "core/x.py": 'print("noisy committed")\n',
    })
    _git(root, "init", "-q")
    _git(root, "add", "-A")
    _git(root, "commit", "-qm", "base")

    # full run sees the committed finding
    assert main(["--root", root]) == 1
    assert "core/x.py" in capsys.readouterr().out

    # changed-only vs HEAD: nothing changed -> clean exit
    assert main(["--root", root, "--changed-only"]) == 0
    capsys.readouterr()

    # an UNTRACKED noisy file is "changed" — only it is reported
    _write(os.path.join(root, "mmlspark_tpu", "core", "y.py"),
           'print("noisy new")\n')
    assert main(["--root", root, "--changed-only"]) == 1
    out = capsys.readouterr().out
    assert "core/y.py" in out and "core/x.py" not in out

    # a MODIFIED tracked file shows up vs the explicit base too
    _write(os.path.join(root, "mmlspark_tpu", "core", "x.py"),
           'print("noisy edited")\n')
    assert main(["--root", root, "--changed-only", "HEAD"]) == 1
    out = capsys.readouterr().out
    assert "core/x.py" in out and "core/y.py" in out


def test_cli_changed_only_git_failure_exits_2(tmp_path, capsys):
    from tools.analyze.__main__ import main

    root = _pkg_tree(tmp_path, {"core/x.py": "x = 1\n"})
    # not a git repo -> git fails -> internal-error exit code
    assert main(["--root", root, "--changed-only"]) == 2
    assert "internal error" in capsys.readouterr().err

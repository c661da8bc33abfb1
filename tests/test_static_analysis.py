"""The static-analysis engine (crdt_enc_tpu/analysis/).

Per-rule positive (seeded violation caught) and negative (compliant
code passes) fixtures, the pragma/baseline suppression round-trips,
the ``--json`` schema golden, the shim exit codes, the live-repo
tier-1 gate (the whole engine must run clean on this repository inside
its runtime budget), and regression tests for the genuine findings
this PR's rules surfaced and fixed (EXC001 silent native fallbacks in
utils/codec.py + ops/columnar.py, OBS001 unaccounted device_put sites
in parallel/{distributed,mesh,session}.py).

Fixtures are parsed, never executed — a fixture may reference jax or
ctypes freely without importing them.
"""

from __future__ import annotations

import importlib.util
import json
import logging
import pathlib
import textwrap
import time

import numpy as np
import pytest

from crdt_enc_tpu.analysis import Baseline, Project, run, unsuppressed_errors
from crdt_enc_tpu.analysis.baseline import parse_toml
from crdt_enc_tpu.analysis.cli import main as cli_main

REPO = pathlib.Path(__file__).resolve().parent.parent

REGISTRY_DOC = textwrap.dedent(
    """\
    # registry fixture

    ## Span registry

    | name | where |
    |---|---|
    | `phase.x` | fixture |
    | `stream.h2d` | fixture |

    ## Counter & gauge registry

    | name | where |
    |---|---|
    | `h2d_bytes` | fixture |
    | `events_dropped` | obs-internal |
    """
)


def analyze(tmp_path, src, rules, *, rel="crdt_enc_tpu/fixture.py",
            registry=True, baseline_text=None):
    """Write a one-file fixture project and run the selected rules."""
    path = tmp_path / rel
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(textwrap.dedent(src))
    doc = tmp_path / "docs" / "observability.md"
    doc.parent.mkdir(exist_ok=True)
    if registry:
        doc.write_text(REGISTRY_DOC)
    baseline = None
    if baseline_text is not None:
        bp = tmp_path / "tools" / "analysis_baseline.toml"
        bp.parent.mkdir(exist_ok=True)
        bp.write_text(textwrap.dedent(baseline_text))
        baseline = Baseline.load(bp)
    # scan (not explicit paths): fixtures must exercise the FULL run
    # semantics, including project-global checks a partial run skips
    project = Project(tmp_path)
    return run(project, rules, baseline), baseline


def errors_of(findings):
    return unsuppressed_errors(findings)


# ------------------------------------------------------------------ FFI001


def test_ffi_partial_binding_caught(tmp_path):
    findings, _ = analyze(
        tmp_path,
        """
        import ctypes
        u8p = ctypes.POINTER(ctypes.c_uint8)
        def _bind(lib):
            lib.half_bound.argtypes = [u8p, ctypes.c_uint64]
        """,
        ["FFI001"],
    )
    msgs = [f.message for f in errors_of(findings)]
    assert any("half_bound" in m and "not restype" in m for m in msgs)


def test_ffi_pointer_without_capacity_caught(tmp_path):
    findings, _ = analyze(
        tmp_path,
        """
        import ctypes
        u8p = ctypes.POINTER(ctypes.c_uint8)
        def _bind(lib):
            lib.unbounded_fill.argtypes = [u8p, u8p]
            lib.unbounded_fill.restype = None
        """,
        ["FFI001"],
    )
    assert any(
        "capacity" in f.message and "unbounded_fill" in f.message
        for f in errors_of(findings)
    )


def test_ffi_discarded_status_and_undeclared_call_caught(tmp_path):
    findings, _ = analyze(
        tmp_path,
        """
        import ctypes
        from . import native
        u8p = ctypes.POINTER(ctypes.c_uint8)
        def _bind(lib):
            lib.checked_fn.argtypes = [u8p, ctypes.c_uint64]
            lib.checked_fn.restype = ctypes.c_int64
        def use():
            lib = native.load()
            lib.checked_fn(None, 0)      # status discarded
            lib.never_declared(None)     # undeclared foreign call
        """,
        ["FFI001"],
    )
    msgs = [f.message for f in errors_of(findings)]
    assert any("discarded" in m for m in msgs)
    assert any("never_declared" in m and "undeclared" in m for m in msgs)


def test_ffi_clean_binding_passes(tmp_path):
    findings, _ = analyze(
        tmp_path,
        """
        import ctypes
        from . import native
        u8p = ctypes.POINTER(ctypes.c_uint8)
        def _bind(lib):
            lib.good_fn.argtypes = [u8p, ctypes.c_uint64]
            lib.good_fn.restype = ctypes.c_int64
        def use():
            lib = native.load()
            rc = lib.good_fn(None, 0)
            if rc != 0:
                raise RuntimeError("native failure")
        """,
        ["FFI001"],
    )
    assert errors_of(findings) == []


def test_ffi_loop_getattr_binding_resolved(tmp_path):
    # the _bind loop form: for name in (...): fn = getattr(lib, name)
    findings, _ = analyze(
        tmp_path,
        """
        import ctypes
        u8p = ctypes.POINTER(ctypes.c_uint8)
        def _bind(lib):
            for name in ("enc_a", "enc_b"):
                fn = getattr(lib, name)
                fn.argtypes = [u8p, ctypes.c_uint64]
                fn.restype = None
        """,
        ["FFI001"],
    )
    assert errors_of(findings) == []


# ------------------------------------------------------------------ JIT001


def test_jit_traced_branch_caught(tmp_path):
    findings, _ = analyze(
        tmp_path,
        """
        import jax
        @jax.jit
        def f(x, y):
            if x > 0:
                return y
            return -y
        """,
        ["JIT001"],
    )
    errs = errors_of(findings)
    assert len(errs) == 1 and "`x`" in errs[0].message


def test_jit_static_and_shape_branches_pass(tmp_path):
    findings, _ = analyze(
        tmp_path,
        """
        import jax
        from functools import partial
        @partial(jax.jit, static_argnames=("mode",))
        def f(x, mode, y=None):
            if mode == "fast":
                x = x * 2
            if y is None:
                y = x
            if x.shape[0] > 8:
                y = y + 1
            if len(x) > 4:
                y = y - 1
            while y.ndim > 2:
                y = y.sum(0)
            return x + y
        """,
        ["JIT001"],
    )
    assert errors_of(findings) == []


# ------------------------------------------------------------------ JIT002


def test_jit_static_value_derived_caught(tmp_path):
    findings, _ = analyze(
        tmp_path,
        """
        import jax
        from functools import partial
        @partial(jax.jit, static_argnames=("num_values",))
        def fold(col, num_values):
            return col
        def caller(col):
            return fold(col, num_values=int(col.max()) + 1)
        """,
        ["JIT002"],
    )
    errs = errors_of(findings)
    assert len(errs) == 1 and "num_values" in errs[0].message


def test_jit_direct_call_decorator_form_resolved(tmp_path):
    """`@jax.jit(static_argnums=...)` (no functools.partial) must be
    recognized — both rules would otherwise skip the function."""
    findings, _ = analyze(
        tmp_path,
        """
        import jax
        @jax.jit(static_argnums=(1,))
        def fold(col, n):
            if col > 0:
                return col
            return -col
        def caller(col):
            return fold(col, int(col.max()))
        """,
        ["JIT001", "JIT002"],
    )
    rules_hit = {f.rule for f in errors_of(findings)}
    assert rules_hit == {"JIT001", "JIT002"}


def test_jit_static_quantized_and_literal_pass(tmp_path):
    findings, _ = analyze(
        tmp_path,
        """
        import jax
        from functools import partial

        def _bucket(n, floor=8):
            return max(floor, 1 << (n - 1).bit_length())

        @partial(jax.jit, static_argnames=("num_members", "num_replicas"))
        def fold(col, num_members, num_replicas):
            return col

        def caller(col, R):
            E = _bucket(len(col))
            fold(col, E, num_replicas=R)   # R: param pass-through
            return fold(col, 128, num_replicas=col.shape[1])
        """,
        ["JIT002"],
    )
    assert errors_of(findings) == []


def test_jit_static_forwarded_through_wrapper_caught(tmp_path):
    """A non-jitted wrapper forwarding its param into a jitted static
    becomes a checked target itself: the raw value is flagged at the
    OUTER call site, not laundered through one level of indirection."""
    findings, _ = analyze(
        tmp_path,
        """
        import jax
        from functools import partial

        def _bucket(n, floor=8):
            return max(floor, 1 << (n - 1).bit_length())

        @partial(jax.jit, static_argnames=("cap",))
        def fold(col, cap):
            return col

        def helper(col, n):
            return fold(col, cap=n)

        def bad(col):
            return helper(col, int(col.max()))

        def good(col):
            return helper(col, _bucket(len(col)))
        """,
        ["JIT002"],
    )
    errs = errors_of(findings)
    assert len(errs) == 1
    assert "`helper`" in errs[0].message and "flows into" in errs[0].message


def test_jit_static_instance_attr_provenance(tmp_path):
    """`self.X` statics are bounded iff every in-class assignment is —
    a raw `col.max()` stashed on the instance is the same recompile
    bug one hop later; quantized/constant attrs and self-referential
    rebinds (`self.E = round_up(self.E)`) stay clean."""
    findings, _ = analyze(
        tmp_path,
        """
        import jax
        from functools import partial

        def _bucket(n, floor=8):
            return max(floor, 1 << (n - 1).bit_length())

        @partial(jax.jit, static_argnames=("cap",))
        def fold(col, cap):
            return col

        class Bad:
            def __init__(self, col):
                self.raw_max = int(col.max())
            def go(self, col):
                return fold(col, cap=self.raw_max)

        class Good:
            def __init__(self, col, mp):
                self.cap = _bucket(len(col))
                self.cap = -(-self.cap // mp) * mp
                self.lim = 128
            def go(self, col):
                return fold(col, cap=self.cap) + fold(col, cap=self.lim)
        """,
        ["JIT002"],
    )
    errs = errors_of(findings)
    assert len(errs) == 1 and "`cap`" in errs[0].message
    assert errs[0].context == "Bad.go"


def test_jit_cross_module_name_collision_not_flagged(tmp_path):
    """Bare-name callee keying must not reach across modules onto an
    unrelated plain function: module b's own `def fold(items, label)`
    shadows module a's jitted `fold` for b's unqualified calls."""
    (tmp_path / "crdt_enc_tpu").mkdir(parents=True)
    (tmp_path / "docs").mkdir()
    (tmp_path / "docs" / "observability.md").write_text(REGISTRY_DOC)
    (tmp_path / "crdt_enc_tpu" / "a.py").write_text(textwrap.dedent(
        """
        import jax
        from functools import partial
        @partial(jax.jit, static_argnames=("n",))
        def fold(col, n):
            return col
        """
    ))
    (tmp_path / "crdt_enc_tpu" / "b.py").write_text(textwrap.dedent(
        """
        def fold(items, label):
            return [label + i for i in items]
        def use(data, tag):
            return fold(data, tag.upper())
        """
    ))
    findings = run(Project(tmp_path), ["JIT002"], None)
    assert errors_of(findings) == []


def test_jit_same_named_wrappers_keep_own_param_orders(tmp_path):
    """Forwarding entries are keyed per owner: module b's 3-param `fold`
    wrapper must not inherit module a's 2-param order (which would
    mis-map positional args into the wrong static slot)."""
    (tmp_path / "crdt_enc_tpu").mkdir(parents=True)
    (tmp_path / "docs").mkdir()
    (tmp_path / "docs" / "observability.md").write_text(REGISTRY_DOC)
    (tmp_path / "crdt_enc_tpu" / "a.py").write_text(textwrap.dedent(
        """
        import jax
        from functools import partial
        @partial(jax.jit, static_argnames=("cap",))
        def jfold(col, cap):
            return col
        def fold(x, cap):
            return jfold(x, cap=cap)
        def use_a(col):
            return fold(col, 64)
        """
    ))
    (tmp_path / "crdt_enc_tpu" / "b.py").write_text(textwrap.dedent(
        """
        import jax
        from functools import partial
        @partial(jax.jit, static_argnames=("n",))
        def jfold2(col, n):
            return col
        def fold(a, b, c):
            return jfold2(a, n=c)
        def benign(col):
            return fold(col, int(col.max()), 8)   # unbounded arg is NOT forwarded
        def guilty(col):
            return fold(col, 1, int(col.max()))   # position 2 IS forwarded
        """
    ))
    findings = run(Project(tmp_path), ["JIT002"], None)
    errs = errors_of(findings)
    assert len(errs) == 1
    assert errs[0].context == "guilty" and "`c`" in errs[0].message


def test_jit_same_named_jitted_defs_resolve_locally(tmp_path):
    """The jitted-callee map is keyed per definition: a module's call to
    its OWN jitted `fold` is checked against that signature, and a bare
    call in a third module that could mean either of two same-named
    jitted defs is skipped rather than checked against a guessed (or
    merged) signature."""
    (tmp_path / "crdt_enc_tpu").mkdir(parents=True)
    (tmp_path / "docs").mkdir()
    (tmp_path / "docs" / "observability.md").write_text(REGISTRY_DOC)
    (tmp_path / "crdt_enc_tpu" / "a.py").write_text(textwrap.dedent(
        """
        import jax
        from functools import partial
        @partial(jax.jit, static_argnames=("n",))
        def fold(col, n):
            return col
        def use_a(col):
            return fold(col, int(col.max()))
        """
    ))
    (tmp_path / "crdt_enc_tpu" / "b.py").write_text(textwrap.dedent(
        """
        import jax
        from functools import partial
        @partial(jax.jit, static_argnames=("mode",))
        def fold(data, mode):
            return data
        """
    ))
    (tmp_path / "crdt_enc_tpu" / "c.py").write_text(textwrap.dedent(
        """
        def use_c(col):
            return fold(col, int(col.max()))   # ambiguous: a's or b's?
        """
    ))
    findings = run(Project(tmp_path), ["JIT002"], None)
    errs = errors_of(findings)
    assert len(errs) == 1
    assert errs[0].path == "crdt_enc_tpu/a.py" and errs[0].context == "use_a"


def test_jit_static_self_referential_local_rebind_passes(tmp_path):
    """`E = -(-E // mp) * mp` after a quantized init (the session.py
    _grow_device_planes shape) must not be flagged: the rebind cycle
    adds no unboundedness — the engine once mistook it for one via the
    recursion depth guard."""
    findings, _ = analyze(
        tmp_path,
        """
        import jax
        from functools import partial

        def _bucket(n, floor=8):
            return max(floor, 1 << (n - 1).bit_length())

        @partial(jax.jit, static_argnames=("cap",))
        def fold(col, cap):
            return col

        def caller(col, mp):
            E = _bucket(len(col))
            E = -(-E // mp) * mp
            return fold(col, cap=E)
        """,
        ["JIT002"],
    )
    assert errors_of(findings) == []


def test_jit_star_unpacked_positions_not_guessed(tmp_path):
    """`fold(*planes, x)` binds x to a position only len(planes) knows —
    mapping by index would check the wrong parameter name (flagging a
    bounded call, or admitting the real static).  Positions past the
    Starred node are skipped; keyword-bound statics are still checked."""
    findings, _ = analyze(
        tmp_path,
        """
        import jax
        from functools import partial
        @partial(jax.jit, static_argnames=("n",))
        def fold(a, n):
            return a
        def caller(col, planes):
            fold(*planes, int(col.max()))
            return fold(*planes, n=int(col.max()))
        """,
        ["JIT002"],
    )
    errs = errors_of(findings)
    assert len(errs) == 1 and "n" in errs[0].message


# ------------------------------------------------------------------ EXC001


def test_exc_silent_native_fallback_caught(tmp_path):
    findings, _ = analyze(
        tmp_path,
        """
        from .. import native
        def fast(buf):
            try:
                lib = native.load()
                return lib.decode(buf)
            except Exception:
                return None
        """,
        ["EXC001"],
    )
    errs = errors_of(findings)
    assert len(errs) == 1 and "silently disable" in errs[0].message


def test_exc_logged_or_reraising_fallback_passes(tmp_path):
    findings, _ = analyze(
        tmp_path,
        """
        import logging
        from .. import native
        logger = logging.getLogger(__name__)

        def _warn_no_native(e):
            logger.warning("native unavailable: %r", e)

        def fast(buf):
            try:
                lib = native.load()
                return lib.decode(buf)
            except Exception as e:
                _warn_no_native(e)
                return None

        def strict(buf):
            try:
                return native.load().decode(buf)
            except Exception as e:
                raise RuntimeError("decode failed") from e

        def unrelated(buf):
            try:
                return int(buf)
            except Exception:
                return None   # no native fast path in the try body
        """,
        ["EXC001"],
    )
    assert errors_of(findings) == []


# ------------------------------------------------------------------ THR001


def test_thread_discipline_caught_and_baseline_pinned(tmp_path):
    src = """
        import threading
        def spawn():
            t1 = threading.Thread(target=print)
            t2 = threading.Thread(target=print)
            return t1, t2
    """
    findings, _ = analyze(tmp_path, src, ["THR001"])
    assert len(errors_of(findings)) == 2

    # a max=1 baseline pin absorbs ONE site; the second still surfaces
    findings, baseline = analyze(
        tmp_path, src, ["THR001"],
        baseline_text="""
        [[suppress]]
        rule = "THR001"
        path = "crdt_enc_tpu/fixture.py"
        context = "spawn"
        reason = "fixture: one sanctioned site"
        max = 1
        """,
    )
    assert len(errors_of(findings)) == 1
    assert baseline.stale_entries() == []


def test_thread_from_import_alias_caught(tmp_path):
    findings, _ = analyze(
        tmp_path,
        """
        from threading import Thread
        def spawn():
            return Thread(target=print)
        """,
        ["THR001"],
    )
    assert len(errors_of(findings)) == 1


def test_thread_module_alias_caught(tmp_path):
    """`import threading as thr; thr.Thread(...)` must not bypass the
    discipline — module aliasing once escaped the rule entirely."""
    findings, _ = analyze(
        tmp_path,
        """
        import threading as thr
        def spawn():
            return thr.Thread(target=print)
        """,
        ["THR001"],
    )
    assert len(errors_of(findings)) == 1


# ------------------------------------------------------------------ SPN001


def test_span_unregistered_name_caught(tmp_path):
    findings, _ = analyze(
        tmp_path,
        """
        from .utils import trace
        def work():
            with trace.span("phase.x"):
                trace.add("not.in.registry", 1)
            with trace.span("stream.h2d"):
                trace.add("h2d_bytes", 1)
        """,
        ["SPN001"],
    )
    errs = errors_of(findings)
    assert len(errs) == 1 and "not.in.registry" in errs[0].message


def test_span_stale_stream_proof_is_error(tmp_path):
    # registry registers stream.h2d but the fixture never emits it
    findings, _ = analyze(
        tmp_path,
        """
        from .utils import trace
        def work():
            trace.add("h2d_bytes", 4)
            with trace.span("phase.x"):
                pass
        """,
        ["SPN001"],
    )
    errs = errors_of(findings)
    assert len(errs) == 1
    assert "stream.h2d" in errs[0].message and errs[0].path.endswith(
        "observability.md"
    )


def test_span_fstring_name_is_warning_not_error(tmp_path):
    findings, _ = analyze(
        tmp_path,
        """
        from .utils import trace
        def work(k):
            trace.add("h2d_bytes", 1)
            with trace.span("phase.x"):
                trace.add(f"chunk.{k}", 1)
            with trace.span("stream.h2d"):
                pass
        """,
        ["SPN001"],
    )
    assert errors_of(findings) == []
    warns = [f for f in findings if f.severity == "warning"]
    assert any("f-string" in f.message for f in warns)


def test_span_qualified_receiver_spelling_matched(tmp_path):
    """The qualified spelling `obs.record.add(...)` hits the same
    matcher as `trace.add(...)` — the old regex lint matched both, and
    SEC001 shares this matcher for its trace-meta sink."""
    findings, _ = analyze(
        tmp_path,
        """
        from . import obs
        def work():
            obs.record.add("not.in.registry", 1)
            obs.record.add("h2d_bytes", 1)
            with obs.record.span("phase.x"):
                pass
            with obs.record.span("stream.h2d"):
                pass
        """,
        ["SPN001"],
    )
    errs = errors_of(findings)
    assert len(errs) == 1 and "not.in.registry" in errs[0].message


def test_span_add_many_literal_keys_are_names(tmp_path):
    """``trace.add_many({...})``: every literal key is a counter name,
    held to the registry and counted as a call site like ``add``'s; a key
    that is no literal is the usual warning."""
    findings, _ = analyze(
        tmp_path,
        """
        from .utils import trace
        def work(name):
            trace.add_many({"h2d_bytes": 1, "not.in.registry": 2, name: 3})
            with trace.span("phase.x"):
                pass
            with trace.span("stream.h2d"):
                pass
        """,
        ["SPN001"],
    )
    errs = errors_of(findings)
    assert len(errs) == 1 and "not.in.registry" in errs[0].message
    warns = [f.message for f in findings if f.severity == "warning"]
    assert "non-literal add_many name" in warns
    assert not any("`h2d_bytes`" in m for m in warns)


# ------------------------------------------------------------------ OBS001


def test_obs_unaccounted_device_put_caught(tmp_path):
    findings, _ = analyze(
        tmp_path,
        """
        import jax
        def upload(x):
            return jax.device_put(x)
        """,
        ["OBS001"],
    )
    assert len(errors_of(findings)) == 1


def test_obs_accounted_device_put_passes(tmp_path):
    findings, _ = analyze(
        tmp_path,
        """
        import jax
        from .utils import trace
        def upload(x):
            trace.add("h2d_bytes", x.nbytes)
            return jax.device_put(x)
        """,
        ["OBS001"],
    )
    assert errors_of(findings) == []


def test_obs_multihost_placement_needs_accounting_too(tmp_path):
    findings, _ = analyze(
        tmp_path,
        """
        import jax
        def upload(sharding, x):
            return jax.make_array_from_process_local_data(sharding, x)
        """,
        ["OBS001"],
    )
    assert len(errors_of(findings)) == 1


def test_obs_module_level_put_needs_module_level_accounting(tmp_path):
    """Accounting inside an unrelated function must not excuse a
    module-level transfer; module-level accounting does."""
    findings, _ = analyze(
        tmp_path,
        """
        import jax
        import numpy as np
        from .utils import trace
        _ZERO = jax.device_put(np.zeros(4))
        def unrelated():
            trace.add("h2d_bytes", 0)
        """,
        ["OBS001"],
    )
    assert len(errors_of(findings)) == 1

    findings, _ = analyze(
        tmp_path,
        """
        import jax
        import numpy as np
        from .utils import trace
        trace.add("h2d_bytes", 16)
        _ZERO = jax.device_put(np.zeros(4))
        """,
        ["OBS001"],
    )
    assert errors_of(findings) == []


def test_obs_unaccounted_jnp_asarray_caught(tmp_path):
    """`jnp.asarray` on host data IS an upload (the ISSUE's
    'jnp.asarray-to-device' half of the invariant); `np.asarray` never
    leaves the host and must not be flagged."""
    findings, _ = analyze(
        tmp_path,
        """
        import jax.numpy as jnp
        import numpy as np
        def to_device(x):
            return jnp.asarray(x)
        def host_only(x):
            return np.asarray(x)
        """,
        ["OBS001"],
    )
    errs = errors_of(findings)
    assert len(errs) == 1 and errs[0].context == "to_device"


def test_obs_asarray_inside_jit_exempt(tmp_path):
    """Inside a jit body `jnp.asarray` is a traced no-op, not a runtime
    transfer — the pallas_merge kernel shape."""
    findings, _ = analyze(
        tmp_path,
        """
        import jax
        import jax.numpy as jnp
        from functools import partial
        @partial(jax.jit, static_argnames=("interpret",))
        def kernel(xs, interpret=False):
            xs = jnp.asarray(xs, jnp.int32)
            return xs
        """,
        ["OBS001"],
    )
    assert errors_of(findings) == []


def test_obs_asarray_in_closure_inside_jit_exempt(tmp_path):
    """A def nested in a jit body (scan/cond body shape) is traced too —
    its jnp.asarray is a no-op; the jit decorator must be found on the
    OUTER function, not just the innermost enclosing def."""
    findings, _ = analyze(
        tmp_path,
        """
        import jax
        import jax.numpy as jnp
        @jax.jit
        def fold(xs):
            def body(carry, x):
                return carry + jnp.asarray(x), None
            return jax.lax.scan(body, jnp.zeros(()), xs)
        """,
        ["OBS001"],
    )
    assert errors_of(findings) == []


def test_obs_scope_excludes_benchmarks(tmp_path):
    findings, _ = analyze(
        tmp_path,
        """
        import jax
        def upload(x):
            return jax.device_put(x)
        """,
        ["OBS001"],
        rel="benchmarks/fixture.py",
    )
    assert errors_of(findings) == []


# ------------------------------------------------------------------ SEC001


def test_sec_key_in_log_and_exception_caught(tmp_path):
    findings, _ = analyze(
        tmp_path,
        """
        import logging
        logger = logging.getLogger(__name__)
        def unwrap(key, blob):
            logger.warning("unwrap failed for key %r", key)
            material = bytes(key)
            raise ValueError(f"bad key material: {material}")
        """,
        ["SEC001"],
    )
    errs = errors_of(findings)
    assert len(errs) == 2
    assert any("log call" in f.message for f in errs)
    assert any("exception message" in f.message for f in errs)


def test_sec_public_facts_about_secrets_pass(tmp_path):
    findings, _ = analyze(
        tmp_path,
        """
        import logging
        logger = logging.getLogger(__name__)
        def unwrap(key, blob):
            if len(key) != 32:
                raise ValueError(f"invalid key length {len(key)}")
            logger.info("unwrapping with key_id %s", key.key_id)
            rc = decrypt(key, blob)          # status code: taint blocked
            logger.debug("decrypt rc=%d", rc)
            return rc
        """,
        ["SEC001"],
    )
    assert errors_of(findings) == []


def test_sec_taint_in_trace_meta_caught(tmp_path):
    findings, _ = analyze(
        tmp_path,
        """
        from .utils import trace
        def seal(passphrase, data):
            with trace.span("phase.x", meta=passphrase):
                return data
        """,
        ["SEC001"],
    )
    assert len(errors_of(findings)) == 1


def test_sec_nonassign_binding_forms_are_sources(tmp_path):
    """Secrets bound via for targets, annotated assignment, or with-as
    must taint like a plain assignment — each of these once escaped the
    rule entirely."""
    findings, _ = analyze(
        tmp_path,
        """
        import logging
        logger = logging.getLogger(__name__)
        def rotate(ring, lockbox, storage):
            for key in ring:
                logger.warning("rotating %r", key)
            passphrase: bytes = storage.load()
            logger.warning("loaded %r", passphrase)
            with lockbox.open() as key_material:
                logger.warning("opened %r", key_material)
        """,
        ["SEC001"],
    )
    errs = errors_of(findings)
    assert len(errs) == 3
    hit = " ".join(f.message for f in errs)
    assert "key" in hit and "passphrase" in hit and "key_material" in hit


def test_sec_loop_carried_taint_reaches_fixpoint(tmp_path):
    """A taint chain assembled against source order (`out = buf` textually
    BEFORE `buf = bytes(key_material)`, loop-carried) still converges —
    a single source-order pass would miss it.  A value derived through a
    non-identity call (`checksum(...)`) stays clean."""
    findings, _ = analyze(
        tmp_path,
        """
        import logging
        logger = logging.getLogger(__name__)
        def drain(key_material, chunks):
            out = b""
            for c in chunks:
                out = buf
                buf = bytes(key_material)
            logger.warning("drained %r", out)
            rc = checksum(key_material)
            logger.debug("checksum rc=%d", rc)
        """,
        ["SEC001"],
    )
    errs = errors_of(findings)
    assert len(errs) == 1
    assert "`out`" in errs[0].message and "log call" in errs[0].message


# ----------------------------------------------------- pragma suppression


def test_pragma_same_line_and_line_above_roundtrip(tmp_path):
    findings, _ = analyze(
        tmp_path,
        """
        import threading
        def spawn():
            t = threading.Thread(target=print)  # lint: disable=THR001
            # lint: disable=THR001
            u = threading.Thread(target=print)
            return t, u
        """,
        ["THR001"],
    )
    assert errors_of(findings) == []
    assert [f.suppressed for f in findings] == ["pragma", "pragma"]


def test_pragma_wrong_rule_does_not_suppress(tmp_path):
    findings, _ = analyze(
        tmp_path,
        """
        import threading
        def spawn():
            return threading.Thread(target=print)  # lint: disable=OBS001
        """,
        ["THR001"],
    )
    assert len(errors_of(findings)) == 1


# ----------------------------------------------------------- baseline file


def test_baseline_contains_and_stale_detection(tmp_path):
    src = """
        import threading
        def spawn():
            return threading.Thread(target=print)
    """
    findings, baseline = analyze(
        tmp_path, src, ["THR001"],
        baseline_text="""
        [[suppress]]
        rule = "THR001"
        path = "crdt_enc_tpu/fixture.py"
        contains = "bare threading.Thread"
        reason = "fixture"

        [[suppress]]
        rule = "THR001"
        path = "crdt_enc_tpu/gone.py"
        reason = "this file no longer exists"
        """,
    )
    assert errors_of(findings) == []
    stale = baseline.stale_entries()
    assert len(stale) == 1 and stale[0].path == "crdt_enc_tpu/gone.py"


def test_baseline_toml_subset_rejects_garbage():
    with pytest.raises(ValueError):
        parse_toml("[[suppress]]\nrule = [1, 2]\n")
    with pytest.raises(ValueError):
        parse_toml("[badtable]\n")
    entries = parse_toml(
        '# comment\n[[suppress]]\nrule = "X"\nmax = 2\n'
    )
    assert entries == [{"rule": "X", "max": 2}]


def test_baseline_hash_inside_quoted_reason_survives():
    entries = parse_toml(
        '[[suppress]]\nrule = "X"\nreason = "see issue #5"  # trailing\n'
    )
    assert entries == [{"rule": "X", "reason": "see issue #5"}]


def test_baseline_unknown_key_rejected(tmp_path):
    """A typo'd narrowing key (`contain` for `contains`) must error, not
    silently widen the suppression to the whole file."""
    bp = tmp_path / "b.toml"
    bp.write_text(
        '[[suppress]]\nrule = "X"\npath = "a.py"\nreason = "r"\n'
        'contain = "oops"\n'
    )
    with pytest.raises(ValueError, match="unknown key"):
        Baseline.load(bp)


# ------------------------------------------------------------- CLI surface


def test_cli_json_schema_golden(tmp_path, capsys):
    (tmp_path / "crdt_enc_tpu").mkdir()
    (tmp_path / "crdt_enc_tpu" / "mod.py").write_text(
        "import threading\n"
        "def spawn():\n"
        "    return threading.Thread(target=print)\n"
    )
    (tmp_path / "docs").mkdir()
    (tmp_path / "docs" / "observability.md").write_text(REGISTRY_DOC)
    rc = cli_main(["--json", "--rule", "THR001", "--root", str(tmp_path)])
    out = json.loads(capsys.readouterr().out)
    assert rc == 1
    assert set(out) == {
        "version", "root", "elapsed_s", "rules", "findings",
        "stale_baseline", "summary",
    }
    assert out["version"] == 2 and out["rules"] == ["THR001"]
    (finding,) = out["findings"]
    # v2: findings carry `chain` (provenance call path; None for
    # single-site rules like THR001)
    assert set(finding) == {
        "rule", "severity", "path", "line", "message", "context",
        "suppressed", "chain",
    }
    assert finding["rule"] == "THR001" and finding["suppressed"] is None
    assert set(out["summary"]) == {
        "files", "errors", "warnings", "suppressed",
    }


def test_cli_unknown_rule_is_usage_error(capsys):
    assert cli_main(["--rule", "NOPE999", "--root", str(REPO)]) == 2


def test_cli_path_subset_skips_project_global_checks(capsys):
    """A single-file run must not report stream.* proof spans as
    unemitted or unrelated baseline entries as stale (they are judged
    against the whole tree, which a path subset doesn't see)."""
    rc = cli_main(
        ["--diff-baseline", "--root", str(REPO),
         str(REPO / "crdt_enc_tpu" / "utils" / "codec.py")]
    )
    out = capsys.readouterr().out
    assert rc == 0
    assert "STALE" not in out and "stream." not in out


def test_cli_path_subset_skips_cross_file_ffi_declarations(capsys):
    """ops/ calls native handles whose argtypes/restype declarations
    live in native/load.py — a path-subset run that can't see the
    declaring module must not report them as undeclared foreign calls
    (same partial-run contract as the stale-span and stale-baseline
    skips).  The full scan still judges them."""
    rc = cli_main(
        ["--root", str(REPO),
         str(REPO / "crdt_enc_tpu" / "ops" / "native_decode.py")]
    )
    out = capsys.readouterr().out
    assert rc == 0
    assert "undeclared foreign call" not in out


def test_cli_out_of_scope_paths_skipped_not_linted(capsys):
    """Explicit paths under exempt trees (tests/ seeds violations on
    purpose) are skipped with a note — a hook feeding changed files must
    not get spurious library-rule errors or a failing exit code."""
    rc = cli_main(
        ["--root", str(REPO), str(REPO / "tests" / "test_obs.py")]
    )
    captured = capsys.readouterr()
    assert rc == 0
    assert "outside the analysis scope" in captured.err
    assert "0 error(s)" in captured.out

    # mixed list: the in-scope file is still analyzed
    rc = cli_main(
        ["--root", str(REPO),
         str(REPO / "tests" / "test_obs.py"),
         str(REPO / "crdt_enc_tpu" / "utils" / "codec.py")]
    )
    captured = capsys.readouterr()
    assert rc == 0
    assert "1 files" in captured.out


def test_cli_directory_arg_expands_to_in_scope_files(tmp_path, capsys):
    """A directory argument means "every in-scope file under it" — it
    must not be classified out-of-scope (no .py suffix) and produce a
    false-clean exit 0 with zero files analyzed."""
    pkg = tmp_path / "crdt_enc_tpu" / "ops"
    pkg.mkdir(parents=True)
    (pkg / "bad.py").write_text(
        textwrap.dedent(
            """
            import threading
            def f():
                threading.Thread(target=print).start()
            """
        )
    )
    (tmp_path / "docs").mkdir()
    rc = cli_main(
        ["--root", str(tmp_path), "--no-baseline", "--rule", "THR001",
         str(tmp_path / "crdt_enc_tpu")]
    )
    captured = capsys.readouterr()
    assert rc == 1
    assert "THR001" in captured.out and "1 files" in captured.out
    assert "outside the analysis scope" not in captured.err

    # a directory wholly outside the scan scope still skips with a note
    tests_dir = tmp_path / "tests"
    tests_dir.mkdir()
    (tests_dir / "test_x.py").write_text("x = 1\n")
    rc = cli_main(
        ["--root", str(tmp_path), "--no-baseline", "--rule", "THR001",
         str(tests_dir)]
    )
    captured = capsys.readouterr()
    assert rc == 0
    assert "contains no in-scope files" in captured.err
    assert "0 files" in captured.out


def test_engine_non_utf8_file_degrades_to_finding(tmp_path):
    """One undecodable file becomes an ENG000 finding; every other file
    is still analyzed (the run must not abort with exit 2)."""
    (tmp_path / "crdt_enc_tpu").mkdir(parents=True)
    (tmp_path / "docs").mkdir()
    (tmp_path / "docs" / "observability.md").write_text(REGISTRY_DOC)
    (tmp_path / "crdt_enc_tpu" / "ok.py").write_text("x = 1\n")
    (tmp_path / "crdt_enc_tpu" / "bad.py").write_bytes(b"# caf\xe9\nx = 1\n")
    project = Project(tmp_path)
    findings = run(project, ["THR001"], None)
    eng = [f for f in findings if f.rule == "ENG000"]
    assert len(eng) == 1 and "UTF-8" in eng[0].message
    assert any(m.rel == "crdt_enc_tpu/ok.py" for m in project.modules)


def test_cli_bad_paths_are_usage_errors(tmp_path, capsys):
    assert cli_main(["--root", str(REPO), "/tmp/does-not-exist-xyz.py"]) == 2
    outside = tmp_path / "outside.py"
    outside.write_text("x = 1\n")
    assert cli_main(["--root", str(REPO), str(outside)]) == 2


def test_cli_non_checkout_root_is_usage_error(tmp_path, capsys):
    """An installed `crdt-analyze` (site-packages root) must say 'pass
    --root', not limp into bogus findings."""
    assert cli_main(["--root", str(tmp_path)]) == 2
    assert "--root" in capsys.readouterr().err


def test_cli_list_rules_names_all_twelve(capsys):
    assert cli_main(["--list-rules"]) == 0
    out = capsys.readouterr().out
    for rule_id in (
        "FFI001", "JIT001", "JIT002", "EXC001", "THR001", "SPN001",
        "OBS001", "SEC001", "ASY001", "DET001", "MUT001", "LCK001",
    ):
        assert rule_id in out


# ------------------------------------------------- live repo: tier-1 gate


def test_live_repo_analysis_clean_within_budget():
    """The tier-1 gate (replaces the old per-script hooks in
    tests/test_obs.py): the whole engine runs clean against the
    committed baseline — no unsuppressed errors, no stale entries —
    inside the 10s budget on this 2-core box."""
    t0 = time.monotonic()
    rc = cli_main(["--diff-baseline", "--root", str(REPO)])
    elapsed = time.monotonic() - t0
    assert rc == 0
    assert elapsed < 10.0, f"analyzer took {elapsed:.1f}s (budget 10s)"


def _load_tool(name: str):
    spec = importlib.util.spec_from_file_location(
        name, REPO / "tools" / f"{name}.py"
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_span_shim_exit_code():
    """tools/check_span_names.py kept its CLI contract (exit 0 clean)."""
    assert _load_tool("check_span_names").main([]) == 0


def test_thread_shim_exit_code():
    """tools/check_thread_discipline.py kept its CLI contract."""
    assert _load_tool("check_thread_discipline").main([]) == 0


# ------------------------------------- regressions for the genuine fixes


def test_codec_native_fallback_warns_once(monkeypatch, caplog):
    """EXC001 fix: losing the native canon_pack logs exactly one warning
    and the Python path still produces canonical bytes."""
    import msgpack

    from crdt_enc_tpu import native
    from crdt_enc_tpu.utils import codec

    monkeypatch.setattr(codec, "_native_pack", None)
    monkeypatch.setattr(
        native, "load_state",
        lambda: (_ for _ in ()).throw(RuntimeError("no build")),
    )
    obj = {b"b": 1, b"a": [2, 3]}
    with caplog.at_level(logging.WARNING, logger="crdt_enc_tpu.codec"):
        out1 = codec.pack(obj)
        out2 = codec.pack(obj)
    warns = [
        r for r in caplog.records if "canon_pack unavailable" in r.message
    ]
    assert len(warns) == 1  # once per process, not per call
    assert out1 == out2
    assert codec.unpack(out1) == msgpack.unpackb(
        out1, raw=False, use_list=False, strict_map_key=False
    )


def test_columnar_native_fallback_warns_once(monkeypatch, caplog):
    """EXC001 fix: the state-assembly fast path failing logs once and
    the caller falls through to the Python path (None sentinel)."""
    from crdt_enc_tpu import native
    from crdt_enc_tpu.ops import columnar

    monkeypatch.setattr(columnar, "_warned_no_native_state", False)
    monkeypatch.setattr(
        native, "load_state",
        lambda: (_ for _ in ()).throw(RuntimeError("no build")),
    )
    empty = np.array([], np.int64)
    with caplog.at_level(logging.WARNING, logger="crdt_enc_tpu.columnar"):
        r1 = columnar._orset_fresh_fold_native(
            None, empty, empty, empty, empty, [], [], empty
        )
        r2 = columnar._orset_fresh_fold_native(
            None, empty, empty, empty, empty, [], [], empty
        )
    assert r1 is None and r2 is None
    warns = [
        r for r in caplog.records
        if "state assembly unavailable" in r.message
    ]
    assert len(warns) == 1


@pytest.mark.parametrize("shape", [(2, 1)])
def test_replicate_and_global_op_batch_account_h2d(shape):
    """OBS001 fix: the distributed placement helpers count their
    transfers at issue."""
    jax = pytest.importorskip("jax")
    from crdt_enc_tpu.parallel import global_op_batch, make_mesh, replicate
    from crdt_enc_tpu.utils import trace

    mesh = make_mesh(shape)
    trace.reset()
    arr = np.arange(64, dtype=np.int32)
    replicate(mesh, arr)
    assert trace.snapshot()["counters"]["h2d_bytes"] == arr.nbytes

    trace.reset()
    kind = np.zeros(8, np.int8)
    member = np.zeros(8, np.int32)
    actor = np.zeros(8, np.int32)
    counter = np.ones(8, np.int32)
    global_op_batch(mesh, kind, member, actor, counter, num_replicas=2)
    # padded to a dp multiple: at least the raw column bytes
    assert trace.snapshot()["counters"]["h2d_bytes"] >= (
        kind.nbytes + member.nbytes + actor.nbytes + counter.nbytes
    )
    trace.reset()


def test_sharded_stream_planes_account_h2d():
    """OBS001 fix: zero-seeded sharded planes count their upload inside
    the helper (the session caller no longer double-counts)."""
    pytest.importorskip("jax")
    from crdt_enc_tpu.parallel import mesh as pmesh
    from crdt_enc_tpu.utils import trace

    m = pmesh.make_mesh((1, 2))
    trace.reset()
    E_pad, R = 8, 2
    clock, add, rm = pmesh.sharded_stream_planes(m, E_pad, R)
    expected = 4 * (max(R, 1) + 2 * E_pad * R)
    assert trace.snapshot()["counters"]["h2d_bytes"] == expected
    assert add.shape == (E_pad, R)
    trace.reset()


def test_orset_merge_many_accounts_host_upload():
    """OBS001 fix: the merge front door's `jnp.asarray` coercion counts
    host-resident stacks at issue; already-device inputs add nothing."""
    pytest.importorskip("jax")
    import jax.numpy as jnp

    from crdt_enc_tpu import ops as K
    from crdt_enc_tpu.utils import trace

    S, E, R = 3, 4, 2
    clocks = np.ones((S, R), np.int32)
    adds = np.ones((S, E, R), np.int32)
    rms = np.zeros((S, E, R), np.int32)

    trace.reset()
    K.orset_merge_many(clocks, adds, rms, impl="tree")
    expected = clocks.nbytes + adds.nbytes + rms.nbytes
    assert trace.snapshot()["counters"]["h2d_bytes"] == expected

    trace.reset()
    K.orset_merge_many(
        jnp.asarray(clocks), jnp.asarray(adds), jnp.asarray(rms), impl="tree"
    )
    assert trace.snapshot()["counters"].get("h2d_bytes", 0) == 0
    trace.reset()

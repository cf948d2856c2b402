"""Graph lint: every AST and IR rule, positive + negative, plus the
``# dkt: ignore`` suppression syntax and the census parser.

Heavier checks against the REAL trainer/serving programs (comm budget,
ZeRO-1 parity, compile counts) live in tests/test_budget_guards.py.
"""

import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distkeras_tpu.analysis.findings import (Finding, apply_suppressions,
                                              suppressed_rules)
from distkeras_tpu.analysis.ir_lint import (CollectiveOp, TraceSpec,
                                             check_budget,
                                             census_to_budget,
                                             check_zero1_parity,
                                             comm_census, lint_trace)
from distkeras_tpu.analysis.source_lint import lint_source


def rules_of(findings, only_gating=False):
    return {f.rule for f in findings if f.gating or not only_gating}


def lint(src, path="distkeras_tpu/models/foo.py"):
    return lint_source(textwrap.dedent(src), path=path)


# ------------------------------------------------------------- AST rules


def test_jit_wallclock_positive_and_negative():
    pos = lint("""
        import time, jax

        @jax.jit
        def step(x):
            t = time.time()
            return x * t
    """)
    assert "jit-wallclock" in rules_of(pos)
    neg = lint("""
        import time, jax

        def host_logger(x):
            return time.time()
    """)
    assert "jit-wallclock" not in rules_of(neg)


def test_jit_np_random_positive_and_negative():
    pos = lint("""
        import jax
        import numpy as np

        def step(x):
            return x + np.random.rand()

        f = jax.jit(step)
    """)
    assert "jit-np-random" in rules_of(pos)
    neg = lint("""
        import numpy as np

        def make_batch(n):
            return np.random.rand(n)
    """)
    assert "jit-np-random" not in rules_of(neg)


def test_traced_detection_reaches_nested_defs():
    pos = lint("""
        import time, jax

        @jax.jit
        def outer(x):
            def inner(y):
                return y + time.time()
            return inner(x)
    """)
    assert "jit-wallclock" in rules_of(pos)


def test_hot_sync_positive_and_negative():
    src = """
        import jax

        def run(losses):
            for l in losses:
                jax.device_get(l)
    """
    assert "hot-sync" in rules_of(
        lint(src, path="distkeras_tpu/trainers/foo.py"))
    # Same code off the hot paths: no finding.
    assert "hot-sync" not in rules_of(
        lint(src, path="distkeras_tpu/data/foo.py"))
    # Hot path but not in a loop: no finding.
    assert "hot-sync" not in rules_of(lint("""
        import jax

        def run(loss):
            jax.device_get(loss)
    """, path="distkeras_tpu/trainers/foo.py"))


def test_import_time_jnp_positive_and_negative():
    pos = lint("""
        import jax.numpy as jnp

        TABLE = jnp.arange(1024)
    """)
    assert "import-time-jnp" in rules_of(pos)
    neg = lint("""
        import jax.numpy as jnp

        def table():
            return jnp.arange(1024)
    """)
    assert "import-time-jnp" not in rules_of(neg)


def test_mutable_default_positive_and_negative():
    pos = lint("""
        def submit(prompt, hooks=[]):
            return hooks
    """)
    assert "mutable-default" in rules_of(pos)
    neg = lint("""
        def submit(prompt, hooks=None):
            return hooks or []

        def _private(prompt, hooks=[]):
            return hooks
    """)
    assert "mutable-default" not in rules_of(neg)


def test_jit_no_donate_positive_and_negative():
    pos = lint("""
        import jax

        def make(train_step):
            return jax.jit(train_step)
    """)
    assert "jit-no-donate" in rules_of(pos)
    neg = lint("""
        import jax

        def make(train_step, loss_fn):
            a = jax.jit(train_step, donate_argnums=0)
            b = jax.jit(loss_fn)
            return a, b
    """)
    assert "jit-no-donate" not in rules_of(neg)


def test_axis_name_positive_and_negative():
    pos = lint("""
        from jax.sharding import PartitionSpec as P

        SPEC = P("dta", None)
    """)
    assert "axis-name" in rules_of(pos)
    neg = lint("""
        from jax.sharding import PartitionSpec as P

        SPEC = P("data", ("model", "seq"))
    """)
    assert "axis-name" not in rules_of(neg)


def test_loop_jit_positive_and_negative():
    pos = lint("""
        import jax

        def compile_all(fns):
            out = []
            for f in fns:
                out.append(jax.jit(f))
            return out
    """)
    assert "loop-jit" in rules_of(pos)
    neg = lint("""
        import jax

        def compile_one(f):
            return jax.jit(f, donate_argnums=0)
    """)
    assert "loop-jit" not in rules_of(neg)


# ---------------------------------------------------- thread-safety rules


def tlint(src, path="distkeras_tpu/serving/foo.py"):
    from distkeras_tpu.analysis.thread_lint import lint_source_threads

    return lint_source_threads(textwrap.dedent(src), path=path)


def test_raw_lock_positive_and_negative():
    src = """
        import threading

        L = threading.Lock()
    """
    assert "raw-lock" in rules_of(tlint(src))
    assert "raw-lock" in rules_of(tlint(
        "import threading\nR = threading.RLock()",
        path="distkeras_tpu/obs/foo.py"))
    # Outside the threaded scope: no finding.
    assert "raw-lock" not in rules_of(
        tlint(src, path="distkeras_tpu/models/foo.py"))
    # The instrumented wrappers are the fix, not a finding.
    assert "raw-lock" not in rules_of(tlint("""
        from distkeras_tpu.utils.locks import TracedLock

        L = TracedLock("x")
    """))
    # ... and their own module is the one allowlisted raw-lock home.
    assert "raw-lock" not in rules_of(tlint(
        "import threading\nL = threading.Lock()",
        path="distkeras_tpu/utils/locks.py"))
    # Every import spelling is caught, not just the literal one.
    assert "raw-lock" in rules_of(tlint("""
        from threading import Lock

        L = Lock()
    """))
    assert "raw-lock" in rules_of(tlint("""
        from threading import RLock as R

        L = R()
    """))
    assert "raw-lock" in rules_of(tlint("""
        import threading as t

        L = t.Condition()
    """))
    # A non-threading Lock name does not fire.
    assert "raw-lock" not in rules_of(tlint("""
        from multiprocessing import Lock

        L = Lock()
    """))


def test_lock_callback_positive_and_negative():
    # The exact PR-8 deadlock shape: subscribers fired under the lock.
    pos = tlint("""
        class T:
            def tick(self):
                with self._lock:
                    for fn in list(self._subscribers):
                        fn(1)
    """)
    assert "lock-callback" in rules_of(pos)
    # Direct call of a callback-named attribute under a lock.
    assert "lock-callback" in rules_of(tlint("""
        class T:
            def fire(self):
                with self._lock:
                    self.on_breach_callback(1)
    """))
    # The fixed shape: collect under the lock, fire after release.
    assert "lock-callback" not in rules_of(tlint("""
        class T:
            def tick(self):
                with self._lock:
                    fired = list(self._subscribers)
                for fn in fired:
                    fn(1)
    """))
    # A def nested under the with runs LATER, not under the lock.
    assert "lock-callback" not in rules_of(tlint("""
        class T:
            def tick(self):
                with self._lock:
                    def later():
                        for fn in list(self._subscribers):
                            fn(1)
                    self.pending = later
    """))


def test_lock_blocking_positive_and_negative():
    assert "lock-blocking" in rules_of(tlint("""
        import time

        def f(lock):
            with lock:
                time.sleep(1.0)
    """))
    assert "lock-blocking" in rules_of(tlint("""
        import subprocess

        def f(lock):
            with lock:
                subprocess.run(["g++"])
    """))
    assert "lock-blocking" in rules_of(tlint("""
        class T:
            def stop(self):
                with self._lock:
                    self._thread.join(timeout=5.0)
    """))
    assert "lock-blocking" in rules_of(tlint("""
        from urllib.request import urlopen

        def f(lock):
            with lock:
                return urlopen("http://peer/metrics").read()
    """))
    # The same calls OFF the lock: no finding.
    assert "lock-blocking" not in rules_of(tlint("""
        import time

        def f(lock):
            with lock:
                n = 1
            time.sleep(1.0)
    """))
    # A string join under a lock is not a thread join.
    assert "lock-blocking" not in rules_of(tlint("""
        def f(lock, parts):
            with lock:
                return ",".join(parts)
    """))


def test_lock_double_acquire_positive_and_negative():
    pos = tlint("""
        from distkeras_tpu.utils.locks import TracedLock

        class T:
            def __init__(self):
                self._lock = TracedLock("t")

            def f(self):
                with self._lock:
                    with self._lock:
                        pass
    """)
    assert "lock-double-acquire" in rules_of(pos)
    # The same nesting on a REENTRANT lock is legal.
    assert "lock-double-acquire" not in rules_of(tlint("""
        from distkeras_tpu.utils.locks import TracedRLock

        class T:
            def __init__(self):
                self._lock = TracedRLock("t")

            def f(self):
                with self._lock:
                    with self._lock:
                        pass
    """))
    # Two DIFFERENT locks nesting: legal.
    assert "lock-double-acquire" not in rules_of(tlint("""
        from distkeras_tpu.utils.locks import TracedLock

        class T:
            def __init__(self):
                self._a = TracedLock("a")
                self._b_lock = TracedLock("b")

            def f(self):
                with self._a:
                    with self._b_lock:
                        pass
    """))
    # An attr name bound reentrant in ONE class and non-reentrant in
    # another is ambiguous, not proof: the reentrant class's legal
    # nesting must not fire.
    assert "lock-double-acquire" not in rules_of(tlint("""
        from distkeras_tpu.utils.locks import TracedLock, TracedRLock

        class A:
            def __init__(self):
                self._lock = TracedRLock("a")

            def f(self):
                with self._lock:
                    with self._lock:
                        pass

        class B:
            def __init__(self):
                self._lock = TracedLock("b")
    """))


def test_thread_lint_suppression_and_severity():
    findings = tlint("""
        import time

        def f(lock):
            with lock:
                time.sleep(0.1)  # dkt: ignore[lock-blocking]
    """)
    hits = [f for f in findings if f.rule == "lock-blocking"]
    assert hits and all(f.suppressed for f in hits)
    assert not [f for f in findings if f.gating]
    # raw-lock / lock-callback / lock-double-acquire are errors
    # (never baselineable); lock-blocking is a warn (ratchets).
    sev = {f.rule: f.severity for f in tlint("""
        import threading, time

        L = threading.Lock()

        def f(lock):
            with lock:
                time.sleep(0.1)
    """)}
    assert sev == {"raw-lock": "error", "lock-blocking": "warn"}


def test_thread_lint_clean_on_repo():
    """The shipped threaded core lints clean — the migration to
    TracedLock is complete and nothing fires callbacks or blocks
    under a lock (zero suppressions; satellite acceptance)."""
    import os

    from distkeras_tpu.analysis.thread_lint import lint_paths_threads

    root = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "distkeras_tpu")
    findings = lint_paths_threads([root])
    gating = [f.format() for f in findings if f.gating]
    assert not gating, gating
    assert not [f for f in findings if f.suppressed], (
        "the concurrency gate ships with zero suppressions")


# ----------------------------------------------------------- suppression


def test_jax_free_positive_and_negative():
    """Round-11 satellite: modules on the jit-free ledger (the live
    telemetry plane, the offline obs modules) must never import jax —
    not even lazily inside a function."""
    src = """
        def handler():
            import jax

            return jax.devices()
    """
    pos = lint(src, path="distkeras_tpu/obs/live.py")
    assert "jax-free" in rules_of(pos, only_gating=True)
    pos = lint("from jax import numpy as jnp",
               path="distkeras_tpu/obs/slo.py")
    assert "jax-free" in rules_of(pos, only_gating=True)
    # Same import outside the ledger: no finding.
    neg = lint(src, path="distkeras_tpu/serving/lanes.py")
    assert "jax-free" not in rules_of(neg)
    # Ledger module importing non-jax things: no finding.
    neg = lint("import json\nimport threading\n",
               path="distkeras_tpu/obs/live.py")
    assert "jax-free" not in rules_of(neg)


def test_jax_free_ledger_covers_live_plane_on_disk():
    """The shipped live-plane modules really are jax-free (the rule
    would gate a regression; this pins the ledger covers them)."""
    import os

    from distkeras_tpu.analysis.source_lint import (_JAX_FREE_FILES,
                                                    lint_paths)

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    paths = [os.path.join(root, f) for f in _JAX_FREE_FILES]
    assert all(os.path.exists(p) for p in paths), paths
    assert {os.path.basename(p) for p in paths} >= {"live.py", "slo.py"}
    findings = lint_paths(paths)
    assert not [f.format() for f in findings if f.rule == "jax-free"]


def test_suppression_comment_parsing():
    assert suppressed_rules("x = 1") is None
    assert suppressed_rules("x = 1  # dkt: ignore") == frozenset()
    assert suppressed_rules("x = 1  # dkt: ignore[a-b, c]") == {"a-b", "c"}


def test_suppression_matching_rule():
    f = Finding(rule="hot-sync", severity="warn", path="p", line=1,
                message="m")
    assert apply_suppressions(f, "foo()  # dkt: ignore[hot-sync]").suppressed
    assert apply_suppressions(f, "foo()  # dkt: ignore").suppressed
    assert not apply_suppressions(f, "foo()  # dkt: ignore[other]").suppressed
    assert not apply_suppressions(f, "foo()").suppressed


def test_source_suppression_end_to_end():
    src = """
        import time, jax

        @jax.jit
        def step(x):
            return x * time.time()  # dkt: ignore[jit-wallclock]
    """
    findings = lint(src)
    assert [f for f in findings if f.rule == "jit-wallclock"]
    assert not [f for f in findings if f.gating]


def test_ir_suppression_via_spec():
    def f(x):
        a = jax.random.normal(x, (4,))
        b = jax.random.normal(x, (4,))
        return a + b

    spec = TraceSpec(name="t", fn=jax.jit(f),
                     args=(jax.random.key(0),),
                     suppress=("prng-reuse",))
    findings, _ = lint_trace(spec, compile_census=False)
    hits = [f for f in findings if f.rule == "prng-reuse"]
    assert hits and all(f.suppressed for f in hits)


# -------------------------------------------------------------- IR rules


def _ir(fn, *args, donate=(), **jit_kw):
    spec = TraceSpec(name="t",
                     fn=jax.jit(fn, donate_argnums=donate, **jit_kw),
                     args=args, donate_argnums=donate)
    findings, _ = lint_trace(spec, compile_census=False)
    return findings


def test_dtype_f64_positive_and_negative():
    with jax.enable_x64(True):
        pos = _ir(lambda x: jnp.asarray(x, jnp.float64) * 2.0,
                  jax.ShapeDtypeStruct((4,), jnp.float32))
    assert "dtype-f64" in rules_of(pos)
    neg = _ir(lambda x: x * 2.0, jax.ShapeDtypeStruct((4,), jnp.float32))
    assert "dtype-f64" not in rules_of(neg)


def test_dtype_upcast_positive_and_negative():
    # Upcast escaping into elementwise math: silent, flagged.
    pos = _ir(lambda x: x.astype(jnp.float32) * 2.0,
              jax.ShapeDtypeStruct((4,), jnp.bfloat16))
    assert "dtype-upcast" in rules_of(pos)
    # f32 ACCUMULATION of a bf16 value (sum's internal promotion) is
    # the standard intentional upcast — exempt.
    neg = _ir(lambda x: x.astype(jnp.bfloat16).sum(),
              jax.ShapeDtypeStruct((4,), jnp.float32))
    assert "dtype-upcast" not in rules_of(neg)


def test_host_callback_positive_and_negative():
    def pos_fn(x):
        jax.debug.print("x={x}", x=x)
        return x + 1

    assert "host-callback" in rules_of(
        _ir(pos_fn, jax.ShapeDtypeStruct((), jnp.float32)))
    assert "host-callback" not in rules_of(
        _ir(lambda x: x + 1, jax.ShapeDtypeStruct((), jnp.float32)))


def test_prng_reuse_positive_and_negative():
    def pos_fn(key):
        a = jax.random.normal(key, (4,))
        b = jax.random.categorical(key, jnp.zeros((8,)))
        return a.sum() + b

    assert "prng-reuse" in rules_of(_ir(pos_fn, jax.random.key(0)))

    def neg_fn(key):
        k1, k2 = jax.random.split(key)
        return (jax.random.normal(k1, (4,)).sum()
                + jax.random.categorical(k2, jnp.zeros((8,))))

    assert "prng-reuse" not in rules_of(_ir(neg_fn, jax.random.key(0)))


def test_prng_loop_invariant_reuse():
    def pos_fn(key, xs):
        def body(c, x):
            return c + jax.random.categorical(key, x), None

        out, _ = jax.lax.scan(body, 0.0, xs)
        return out

    xs = jax.ShapeDtypeStruct((3, 8), jnp.float32)
    assert "prng-reuse" in rules_of(_ir(pos_fn, jax.random.key(0), xs))

    def neg_fn(key, xs):
        def body(c, ix):
            i, x = ix
            k = jax.random.fold_in(key, i)
            return c + jax.random.categorical(k, x), None

        out, _ = jax.lax.scan(body, 0.0, (jnp.arange(3), xs))
        return out

    assert "prng-reuse" not in rules_of(
        _ir(neg_fn, jax.random.key(0), xs))

    def neg_presplit(key, xs):
        # The textbook pattern: scan OVER pre-split keys — each
        # iteration's key varies, nothing is loop-invariant.
        ks = jax.random.split(key, 3)

        def body(c, kx):
            k, x = kx
            return c + jax.random.categorical(k, x), None

        out, _ = jax.lax.scan(body, 0.0, (ks, xs))
        return out

    assert "prng-reuse" not in rules_of(
        _ir(neg_presplit, jax.random.key(0), xs))


def test_prng_cond_branches_are_exclusive():
    def neg_fn(pred, key):
        # Only one branch runs: consuming the key once in EACH branch
        # is exactly one consumption at runtime.
        return jax.lax.cond(
            pred,
            lambda k: jax.random.normal(k, (4,)),
            lambda k: jax.random.uniform(k, (4,)),
            key)

    assert "prng-reuse" not in rules_of(
        _ir(neg_fn, jax.ShapeDtypeStruct((), jnp.bool_),
            jax.random.key(0)))

    def pos_fn(pred, key):
        # Consumed before the cond AND inside a branch: real reuse.
        a = jax.random.normal(key, (4,))
        b = jax.lax.cond(
            pred,
            lambda k: jax.random.normal(k, (4,)),
            lambda k: jnp.zeros((4,)),
            key)
        return a + b

    assert "prng-reuse" in rules_of(
        _ir(pos_fn, jax.ShapeDtypeStruct((), jnp.bool_),
            jax.random.key(0)))


def test_donation_unused_positive_and_negative():
    pos = _ir(lambda x: (x * 2.0).sum(),
              jax.ShapeDtypeStruct((8,), jnp.float32), donate=(0,))
    assert "donation-unused" in rules_of(pos)
    neg = _ir(lambda x: x * 2.0,
              jax.ShapeDtypeStruct((8,), jnp.float32), donate=(0,))
    assert "donation-unused" not in rules_of(neg)


def test_donation_read_positive_and_negative():
    pos = _ir(lambda x: (x, x + 1.0),
              jax.ShapeDtypeStruct((8,), jnp.float32), donate=(0,))
    assert "donation-read" in rules_of(pos)
    neg = _ir(lambda x: (x + 1.0, x.sum()),
              jax.ShapeDtypeStruct((8,), jnp.float32), donate=(0,))
    assert "donation-read" not in rules_of(neg)


# ------------------------------------------------------- census + budget


_SYNTH_HLO = """\
HloModule synth

%add (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %s = f32[] add(f32[] %a, f32[] %b)
}

%fused_computation.1 (p0: f32[128], p1: s32[]) -> f32[16] {
  %p0 = f32[128]{0} parameter(0)
  %p1 = s32[] parameter(1)
  ROOT %ds = f32[16]{0} dynamic-slice(f32[128]{0} %p0, s32[] %p1), dynamic_slice_sizes={16}
}

ENTRY %main.1 (g: f32[128], x: f32[1,16], y: f32[128], l: f32[]) -> f32[16] {
  %g = f32[128]{0} parameter(0)
  %x = f32[1,16]{1,0} parameter(1)
  %y = f32[128]{0} parameter(2)
  %l = f32[] parameter(3)
  %all-reduce = f32[128]{0} all-reduce(f32[128]{0} %g), channel_id=1, replica_groups=[1,8]<=[8], to_apply=%add
  %pid = s32[] partition-id()
  %use = f32[16]{0} fusion(f32[128]{0} %all-reduce, s32[] %pid), kind=kLoop, calls=%fused_computation.1
  %all-reduce.1 = f32[] all-reduce(f32[] %l), channel_id=2, replica_groups=[1,8]<=[8], to_apply=%add
  %b = f32[16]{0} broadcast(f32[] %all-reduce.1), dimensions={}
  %all-gather = f32[8,16]{1,0} all-gather(f32[1,16]{1,0} %x), channel_id=3, replica_groups={{0,1,2,3,4,5,6,7}}, dimensions={0}
  %reduce-scatter = f32[16]{0} reduce-scatter(f32[128]{0} %y), channel_id=4, replica_groups=[1,8]<=[8], dimensions={0}, to_apply=%add
  ROOT %out = f32[16]{0} add(f32[16]{0} %use, f32[16]{0} %b)
}
"""


def test_comm_census_parses_and_canonicalizes():
    census = {(c.op, c.canonical): c
              for c in comm_census(_SYNTH_HLO, default_group=8)}
    # The gradient AR's only consumer slices 1/8 of it -> canonical RS.
    ar_rs = census[("all-reduce", "reduce-scatter")]
    assert ar_rs.payload_bytes == 512 and ar_rs.wire_bytes == 448.0
    # The loss AR's consumer broadcasts (no slice) -> stays AR.
    ar = census[("all-reduce", "all-reduce")]
    assert ar.payload_bytes == 4
    ag = census[("all-gather", "all-gather")]
    assert ag.payload_bytes == 512 and ag.wire_bytes == 448.0
    rs = census[("reduce-scatter", "reduce-scatter")]
    # Payload = the full pre-scatter operand, not the 1/8 result.
    assert rs.payload_bytes == 512 and rs.wire_bytes == 448.0


def test_budget_check_positive_and_negative():
    census = comm_census(_SYNTH_HLO, default_group=8)
    good = {"t": census_to_budget(census)}
    assert check_budget("t", census, good) == []
    drifted = {"t": {"collectives": [], "wire_total": 0}}
    bad = check_budget("t", census, drifted)
    assert [f for f in bad if f.rule == "comm-budget" and f.gating]
    missing = check_budget("other", census, good)
    assert [f for f in missing if f.rule == "comm-budget"]


def test_zero1_parity_needs_reference_bytes():
    spec = TraceSpec(name="z", fn=jax.jit(lambda x: x), args=(1.0,))
    findings = check_zero1_parity(spec, [])
    assert "zero1-parity" in rules_of(findings)


def test_zero1_parity_detects_missing_exchange():
    # A step with NO declared zero1 exchange must fail parity loudly.
    spec = TraceSpec(name="z", fn=jax.jit(lambda x: x * 2.0),
                     args=(jnp.ones((8,)),), params_bytes=32)
    dp_census = [CollectiveOp(op="all-reduce", canonical="all-reduce",
                              payload_bytes=32, group_size=8)]
    findings = check_zero1_parity(spec, dp_census)
    assert "zero1-parity" in rules_of(findings)


# ------------------------------------------------------ warn baselines


def _warn(rule, path, line=1):
    return Finding(rule=rule, severity="warn", path=path, line=line,
                   message="m")


def test_baseline_ratchet_covers_and_gates():
    """Per-finding baselines (round-10 satellite): warn findings
    covered by the ledger stop gating, the EXCESS beyond a key's
    recorded count still gates, unrecorded keys gate, and errors are
    never baselineable."""
    from distkeras_tpu.analysis.findings import (apply_baseline,
                                                 baseline_key,
                                                 warn_counts)

    fs = [_warn("hot-sync", "a.py"), _warn("hot-sync", "a.py", 9),
          _warn("loop-jit", "b.py"),
          Finding(rule="jit-wallclock", severity="error", path="a.py",
                  line=2, message="m")]
    ledger = {baseline_key(fs[0]): 1, baseline_key(fs[2]): 1}
    out = apply_baseline(fs, ledger)
    # One of the two hot-sync findings is covered; the second gates.
    hot = [f for f in out if f.rule == "hot-sync"]
    assert sorted(f.baselined for f in hot) == [False, True]
    assert [f for f in out if f.rule == "hot-sync" and f.gating]
    assert not next(f for f in out if f.rule == "loop-jit").gating
    # The error is untouched and still gates.
    err = next(f for f in out if f.severity == "error")
    assert err.gating and not err.baselined
    assert "(baselined)" in next(f for f in out if f.baselined).format()
    # An empty ledger is the pre-baseline behavior: every warn gates.
    assert all(f.gating for f in apply_baseline(fs, {})
               if f.severity == "warn")
    # Census counts only unsuppressed warns (what --update records).
    counts = warn_counts(fs + [dataclasses_replace_suppressed(fs[0])])
    assert counts[baseline_key(fs[0])] == 2
    assert baseline_key(err) not in counts


def dataclasses_replace_suppressed(f):
    import dataclasses

    return dataclasses.replace(f, suppressed=True)


def test_baseline_roundtrip_and_missing_file(tmp_path):
    from distkeras_tpu.analysis.findings import (load_baseline,
                                                 save_baseline)

    path = str(tmp_path / "lint_baseline.json")
    assert load_baseline(path) == {}       # missing = empty ledger
    fs = [_warn("hot-sync", "a.py"), _warn("hot-sync", "a.py", 7)]
    counts = save_baseline(path, fs)
    assert counts == {"hot-sync:a.py": 2}
    assert load_baseline(path) == counts


def test_graph_lint_cli_update_baseline(tmp_path):
    """scripts/graph_lint.py --update-baseline writes the ledger (the
    repo is warn-clean, so it records an empty census) and the normal
    run reads it."""
    import json
    import os
    import subprocess
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    ledger = os.path.join(root, "scripts", "lint_baseline.json")
    assert os.path.exists(ledger), "ship the (possibly empty) ledger"
    with open(ledger) as fh:
        data = json.load(fh)
    assert "warn_counts" in data
    # The checked-in ledger must already be the ratchet floor: a full
    # --source-only run against it is clean (subprocess keeps this
    # hermetic; the IR half is covered by test_budget_guards).
    r = subprocess.run(
        [sys.executable, os.path.join(root, "scripts", "graph_lint.py"),
         "--source-only"], capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stdout + r.stderr
    # Re-recording from a half-census would drop the other layer's
    # keys: the CLI refuses the combination.
    r = subprocess.run(
        [sys.executable, os.path.join(root, "scripts", "graph_lint.py"),
         "--source-only", "--update-baseline"],
        capture_output=True, text=True, timeout=300)
    assert r.returncode != 0 and "full run" in r.stderr


# ----------------------------------------------------- repo runs clean


def test_source_lint_clean_on_repo():
    import os

    from distkeras_tpu.analysis.source_lint import lint_paths

    root = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "distkeras_tpu")
    findings = lint_paths([root])
    gating = [f.format() for f in findings if f.gating]
    assert not gating, gating

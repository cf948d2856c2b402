"""IR lint: audit the trainers' REAL compiled step programs.

A :class:`TraceSpec` names one jitted function plus example arguments
(shape structs are fine — nothing executes).  :func:`lint_trace` then

* traces it to a closed jaxpr and walks every sub-jaxpr for the
  **dtype policy** (f64 anywhere; silent bf16/f16 -> f32 upcasts),
  **host callbacks** inside the jit region, and **PRNG key reuse**
  (one key consumed by two samplers with no ``split``/``fold_in``
  between, or sampled loop-invariantly inside a scan/while body);
* lowers + compiles it and parses the post-SPMD HLO into a
  **collective census** (:func:`comm_census`) — op kind, payload
  bytes, replica-group size, and ring-model wire bytes per device —
  the number ``scripts/comm_budget.json`` pins in CI;
* checks **donation coverage**: declared-donated buffers that XLA
  could not consume (lower-time warning), and donated inputs that are
  both read and returned (XLA inserts a copy — the donation buys
  nothing).

Census canonicalization.  XLA's CPU pipeline lacks the
reduce-scatter-creator pass GPU/TPU partitioners run, so a GSPMD
reduce-scatter compiles on the test mesh as ``all-reduce`` followed by
each device slicing its own 1/n chunk.  When every consumer of an
all-reduce provably uses at most a 1/n slice (the consumer is a
``dynamic-slice``, or a fusion whose body slices, with output bytes
<= payload/n), the census records the op with ``canonical:
"reduce-scatter"`` and charges reduce-scatter wire volume — the bytes
any production partitioner (and the wire) would actually move.  The
raw opcode is kept alongside, so the budget diff shows both.

Wire model (ring algorithms, group size n): all-reduce moves
``2(n-1)/n * payload`` per device, reduce-scatter and all-gather
``(n-1)/n * payload``, collective-permute ``payload``.  This is what
makes the ZeRO-1 claim checkable: RS(G) + AG(G) == AR(G) exactly.
"""

from __future__ import annotations

import dataclasses
import json
import re
import warnings
from typing import Any, Callable, Sequence

import jax
import numpy as np
from jax.extend.core import Literal

from distkeras_tpu.analysis.findings import Finding

# ------------------------------------------------------------------ specs


@dataclasses.dataclass(frozen=True)
class TraceSpec:
    """One jitted function the IR lint should reach.

    ``fn`` must be the *real* jitted callable the subsystem executes
    (the ``traced_for_analysis()`` hooks hand these out), so the lint
    sees production donation/sharding flags, not a reimplementation.
    ``args`` may mix concrete arrays, ``ShapeDtypeStruct``s and None.
    ``suppress`` is the IR layer's ignore syntax: rule ids waived for
    this target (the per-line ``# dkt: ignore[...]`` form has no
    single line to attach to in a compiled program).
    """

    name: str
    fn: Callable
    args: tuple
    # The donate_argnums the hook passed to jax.jit — carried
    # explicitly (jit wrappers do not expose them portably).
    donate_argnums: tuple = ()
    suppress: tuple = ()
    # Total parameter bytes of the model this step trains (the hooks
    # fill it in) — the zero parity check's reference volume P.
    params_bytes: int | None = None
    # The DP partner target whose gradient all-reduce this target's
    # declared RS+AG exchange must replace at equal volume.
    zero1_parity_with: str | None = None
    # Which ZeRO stage's declared scopes to measure (1: the post-scan
    # RS + explicit AG; 2: the in-scan accumulator RS + update AG; 3:
    # the gather-on-use AG + backward grad RS).
    zero_stage: int = 1


@dataclasses.dataclass(frozen=True)
class CollectiveOp:
    """One collective in the compiled program (aggregated by kind).

    ``dtype`` is the HLO element type of the payload ("f32", "s8",
    "bf16", ... — "+"-joined when a tuple-shaped collective mixes
    types).  Payload bytes were always computed from the compiled
    shapes, so compressed exchanges were never *miscounted*; recording
    the dtype makes the budget PROVE the wire carries int8, not f32 —
    a census that only showed byte totals could silently pass an
    exchange that decompressed before the wire.
    """

    op: str               # HLO opcode as compiled
    canonical: str        # opcode after AR+slice canonicalization
    payload_bytes: int
    group_size: int
    count: int = 1
    dtype: str = "f32"

    @property
    def wire_bytes(self) -> float:
        """Ring-model per-device wire bytes for ``count`` ops."""
        n = max(self.group_size, 1)
        per = {
            "all-reduce": 2 * (n - 1) / n * self.payload_bytes,
            "reduce-scatter": (n - 1) / n * self.payload_bytes,
            "all-gather": (n - 1) / n * self.payload_bytes,
            "all-to-all": (n - 1) / n * self.payload_bytes,
            "collective-permute": float(self.payload_bytes),
        }.get(self.canonical, float(self.payload_bytes))
        return per * self.count

    def as_json(self) -> dict:
        return {"op": self.op, "canonical": self.canonical,
                "dtype": self.dtype,
                "payload_bytes": self.payload_bytes,
                "group_size": self.group_size, "count": self.count,
                "wire_bytes": round(self.wire_bytes, 1)}


# ------------------------------------------------------------ HLO parsing

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2,
    "bf16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
    "f64": 8, "c64": 8, "c128": 16,
}

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
                "all-to-all", "collective-permute")

_INSTR_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%(?P<name>[\w.\-]+)\s*=\s*(?P<rhs>.*)$")
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_COMP_RE = re.compile(r"^(?:ENTRY\s+)?%(?P<name>[\w.\-]+)\s+\(.*\)\s+->")


def _shape_bytes(segment: str) -> int:
    total = 0
    for dtype, dims in _SHAPE_RE.findall(segment):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


def _shape_dtypes(segment: str) -> str:
    """Element type(s) of a shape segment: "f32", "s8", ... — ordered,
    de-duplicated, "+"-joined for tuple shapes mixing types ("?" when
    no shape parses).  The census field that distinguishes an int8
    compressed payload from the f32 it replaced."""
    seen = []
    for dtype, _ in _SHAPE_RE.findall(segment):
        if dtype in _DTYPE_BYTES and dtype not in seen:
            seen.append(dtype)
    return "+".join(seen) or "?"


def _group_size(line: str, default: int) -> int:
    m = re.search(r"replica_groups=\[(\d+),(\d+)\]", line)
    if m:
        return int(m.group(2))
    m = re.search(r"replica_groups=\{\{([^}]*)\}", line)
    if m:
        return m.group(1).count(",") + 1
    return default


@dataclasses.dataclass
class _Instr:
    name: str
    opcode: str
    result_seg: str
    operand_refs: tuple
    calls: str | None
    line: str
    computation: str


def _parse_instrs(hlo: str) -> tuple[dict, dict]:
    """HLO text -> ({instr name: _Instr}, {computation name: body text}).

    Text-level, deliberately: the census needs opcodes, shapes,
    operand references and fusion bodies — all stable in HLO dumps —
    and must not depend on XLA python bindings.
    """
    instrs: dict[str, _Instr] = {}
    comps: dict[str, list] = {}
    current = "main"
    for raw in hlo.splitlines():
        cm = _COMP_RE.match(raw.strip())
        if cm and raw.rstrip().endswith("{"):
            current = cm.group("name")
            comps[current] = []
            continue
        comps.setdefault(current, []).append(raw)
        m = _INSTR_RE.match(raw)
        if not m:
            continue
        rhs = m.group("rhs")
        om = re.search(r"(?:^|\)\s|\}\s|\]\s|\s)([a-z][a-z0-9\-]*)\(", rhs)
        if not om:
            continue
        opcode = om.group(1)
        result_seg = rhs[:om.start(1)]
        # Data operands: the first balanced paren group after the
        # opcode.  Attribute refs (calls=%c, to_apply=%r) come later.
        depth, start, end = 0, om.end(1), None
        for i in range(om.end(1), len(rhs)):
            if rhs[i] == "(":
                depth += 1
            elif rhs[i] == ")":
                depth -= 1
                if depth == 0:
                    end = i
                    break
        operands = rhs[om.end(1) + 1:end] if end else ""
        refs = tuple(re.findall(r"%([\w.\-]+)", operands))
        calls = re.search(r"calls=%([\w.\-]+)", rhs)
        instrs[m.group("name")] = _Instr(
            name=m.group("name"), opcode=opcode, result_seg=result_seg,
            operand_refs=refs, calls=calls.group(1) if calls else None,
            line=raw, computation=current)
    return instrs, {k: "\n".join(v) for k, v in comps.items()}


def _consumes_sliced(instr: _Instr, comps: dict) -> bool:
    """Does ``instr`` read only a slice of its operand?  True for a
    dynamic-slice, or a fusion whose body dynamic-slices."""
    if instr.opcode == "dynamic-slice":
        return True
    if instr.opcode == "fusion" and instr.calls:
        return "dynamic-slice(" in comps.get(instr.calls, "")
    return False


def comm_census(hlo: str, default_group: int | None = None
                ) -> list[CollectiveOp]:
    """Collective census of one compiled HLO module, aggregated by
    (canonical op, payload, group).  See the module docstring for the
    AR -> reduce-scatter canonicalization rule."""
    if default_group is None:
        default_group = jax.device_count()
    instrs, comps = _parse_instrs(hlo)
    raw: list[CollectiveOp] = []
    for ins in instrs.values():
        op = ins.opcode
        if op.endswith("-start"):
            op = op[:-len("-start")]
        if op not in _COLLECTIVES:
            continue
        n = _group_size(ins.line, default_group)
        if op == "reduce-scatter":
            # Payload = the full pre-scatter operand (what the ring
            # carries), not the 1/n result.
            payload = _operand_bytes(ins)
        else:
            payload = _shape_bytes(ins.result_seg)
        dtype = _shape_dtypes(ins.result_seg)
        canonical = op
        if op == "all-reduce" and n > 1:
            consumers = [c for c in instrs.values()
                         if ins.name in c.operand_refs
                         and c.computation == ins.computation]
            if consumers and all(
                    _consumes_sliced(c, comps)
                    and _shape_bytes(c.result_seg) * n <= payload
                    for c in consumers):
                canonical = "reduce-scatter"
        raw.append(CollectiveOp(op=op, canonical=canonical,
                                payload_bytes=payload, group_size=n,
                                dtype=dtype))
    # Aggregate identical ops so the census is order-stable.
    agg: dict[tuple, int] = {}
    for c in raw:
        key = (c.op, c.canonical, c.payload_bytes, c.group_size, c.dtype)
        agg[key] = agg.get(key, 0) + 1
    return [CollectiveOp(op=k[0], canonical=k[1], payload_bytes=k[2],
                         group_size=k[3], dtype=k[4], count=v)
            for k, v in sorted(agg.items())]


def _operand_bytes(ins: _Instr) -> int:
    """Total bytes of an instruction's data operands (shapes are
    inlined in the operand list: ``reduce-scatter(f32[64]{0} %x)``)."""
    seg = ins.line.split(ins.opcode + "(", 1)
    if len(seg) < 2:
        return _shape_bytes(ins.result_seg)
    depth, out = 1, []
    for ch in seg[1]:
        if ch == "(":
            depth += 1
        elif ch == ")":
            depth -= 1
            if depth == 0:
                break
        out.append(ch)
    return _shape_bytes("".join(out))


def census_wire_total(census: Sequence[CollectiveOp]) -> float:
    return round(sum(c.wire_bytes for c in census), 1)


# ------------------------------------------------------------ jaxpr walk


def _subjaxprs(eqn):
    """(inner jaxpr, outer->inner var mapping) pairs for every
    call-like param of ``eqn`` — pjit, scan, while, cond, shard_map,
    custom_*; the var mapping keeps PRNG identity flowing across the
    boundary when arities line up (unknown layouts map nothing —
    conservative, never a false alias)."""
    if eqn.primitive.name == "while":
        # invars = [cond_consts..., body_consts..., carry...]; the two
        # jaxprs see different slices — align each explicitly.
        nc = eqn.params.get("cond_nconsts", 0)
        nb = eqn.params.get("body_nconsts", 0)
        cond, body = eqn.params["cond_jaxpr"], eqn.params["body_jaxpr"]
        carry = eqn.invars[nc + nb:]
        return [
            (cond.jaxpr, dict(zip(cond.jaxpr.invars,
                                  list(eqn.invars[:nc]) + list(carry)))),
            (body.jaxpr, dict(zip(body.jaxpr.invars,
                                  list(eqn.invars[nc:nc + nb])
                                  + list(carry)))),
        ]
    out = []
    for val in eqn.params.values():
        vals = val if isinstance(val, (tuple, list)) else (val,)
        for v in vals:
            jaxpr = getattr(v, "jaxpr", None)
            if jaxpr is None and hasattr(v, "eqns"):
                jaxpr = v
            if jaxpr is None:
                continue
            if len(jaxpr.invars) == len(eqn.invars):
                mapping = dict(zip(jaxpr.invars, eqn.invars))
            elif len(eqn.invars) > len(jaxpr.invars):
                # cond branches (pred leads), while bodies: inner
                # invars align with the TAIL of the outer operands.
                mapping = dict(zip(jaxpr.invars,
                                   eqn.invars[-len(jaxpr.invars):]))
            else:
                mapping = {}
            out.append((jaxpr, mapping))
    return out


_PRNG_CONSUMING = {"random_bits", "random_gamma"}
_LOOP_PRIMS = {"scan", "while"}


def _is_key(var) -> bool:
    dtype = getattr(getattr(var, "aval", None), "dtype", None)
    try:
        return dtype is not None and jax.numpy.issubdtype(
            dtype, jax.dtypes.prng_key)
    except TypeError:
        return False


def _audit_jaxpr(closed, spec: TraceSpec) -> list[Finding]:
    findings: list[Finding] = []
    seen_rules: set[tuple] = set()

    def add(rule, severity, message, hint=""):
        key = (rule, message)
        if key in seen_rules:
            return
        seen_rules.add(key)
        findings.append(Finding(
            rule=rule, severity=severity, path=spec.name, line=None,
            message=message, hint=hint,
            suppressed=rule in spec.suppress))

    # PRNG bookkeeping: canonical identity per key var (flow through
    # sub-jaxpr boundaries), sampler-consumption counts, and the set of
    # identities that entered a loop body as loop-invariant captures.
    root_of: dict = {}
    consumed: dict = {}

    def root(v):
        return root_of.setdefault(v, v)

    # f32 ACCUMULATION of a low-precision value is the standard,
    # intentional upcast (sum/mean/argmax promote internally); only
    # upcasts that escape into non-reduction math are "silent".
    reductions = {"reduce_sum", "reduce_prod", "reduce_max",
                  "reduce_min", "argmax", "argmin", "reduce_precision"}

    def walk(jaxpr, in_loop: frozenset):
        uses: dict = {}
        for eqn in jaxpr.eqns:
            for v in eqn.invars:
                if not isinstance(v, Literal):
                    uses.setdefault(v, []).append(eqn.primitive.name)
        for eqn in jaxpr.eqns:
            prim = eqn.primitive.name
            for v in eqn.outvars:
                dtype = getattr(getattr(v, "aval", None), "dtype", None)
                if dtype is not None and str(dtype) in ("float64",
                                                        "complex128"):
                    add("dtype-f64", "error",
                        f"f64 value produced by `{prim}`",
                        "the repo's dtype policy is f32/bf16 compute; "
                        "enable-x64 leaks or np.float64 literals "
                        "usually cause this")
            if prim == "convert_element_type":
                src = getattr(eqn.invars[0].aval, "dtype", None)
                dst = eqn.params.get("new_dtype")
                consumers = uses.get(eqn.outvars[0], [])
                accum_only = bool(consumers) and all(
                    c in reductions for c in consumers)
                if (src is not None and str(src) in ("bfloat16", "float16")
                        and str(dst) in ("float32", "float64")
                        and not accum_only):
                    add("dtype-upcast", "warn",
                        f"silent {src} -> {dst} upcast in the traced "
                        "program",
                        "on a low-precision compute path an upcast "
                        "doubles the bytes XLA moves; cast explicitly "
                        "where precision is required and keep the rest "
                        "low-precision")
            if prim.endswith("callback") or prim in (
                    "outside_call", "host_callback_call", "debug_print"):
                add("host-callback", "warn",
                    f"host callback `{prim}` inside the jit region",
                    "each call is a device->host round-trip per "
                    "execution; hoist it out of the step or gate it "
                    "behind a debug flag")
            # PRNG: samplers consume; split/fold_in derive fresh keys.
            if prim in _PRNG_CONSUMING:
                for v in eqn.invars:
                    if not _is_key(v):
                        continue
                    r = root(v)
                    consumed[r] = consumed.get(r, 0) + 1
                    if consumed[r] > 1:
                        add("prng-reuse", "error",
                            "one PRNG key is consumed by two samplers "
                            "with no split/fold_in between",
                            "correlated draws: derive a fresh key per "
                            "sampler (jax.random.split / fold_in)")
                    elif r in in_loop:
                        add("prng-reuse", "error",
                            "a loop-invariant PRNG key is consumed "
                            "inside a scan/while body",
                            "every iteration redraws the same bits; "
                            "fold the loop index into the key first")
            inner_loop = in_loop
            if prim in _LOOP_PRIMS:
                # Only the truly loop-INVARIANT key inputs — the
                # leading consts (scan) / cond+body consts (while).
                # The carry and scanned-over xs vary per iteration, so
                # scanning over pre-split keys is the CORRECT pattern
                # and must not flag.
                if prim == "scan":
                    n_inv = eqn.params.get("num_consts", 0)
                else:
                    n_inv = (eqn.params.get("cond_nconsts", 0)
                             + eqn.params.get("body_nconsts", 0))
                inner_loop = in_loop | frozenset(
                    root(v) for v in eqn.invars[:n_inv] if _is_key(v))
            subs = _subjaxprs(eqn)
            if prim == "cond":
                # Branches are mutually exclusive at runtime: count
                # each from the same baseline and keep the per-key
                # MAX, or a key consumed once in every branch would
                # read as reuse.
                base = dict(consumed)
                merged = dict(base)
                for sub, mapping in subs:
                    for inner_v, outer_v in mapping.items():
                        if _is_key(inner_v) or _is_key(outer_v):
                            root_of[inner_v] = root(outer_v)
                    consumed.clear()
                    consumed.update(base)
                    walk(sub, inner_loop)
                    for key_root, n in consumed.items():
                        merged[key_root] = max(merged.get(key_root, 0),
                                               n)
                consumed.clear()
                consumed.update(merged)
            else:
                for sub, mapping in subs:
                    for inner_v, outer_v in mapping.items():
                        if _is_key(inner_v) or _is_key(outer_v):
                            root_of[inner_v] = root(outer_v)
                    walk(sub, inner_loop)

    walk(closed.jaxpr, frozenset())
    return findings


# ---------------------------------------------------------- donation


def _donated_flat_indices(spec: TraceSpec) -> list[int]:
    """Flat invar indices of the donated argument leaves, from the
    spec's donate_argnums and the example args' pytree shapes."""
    argnums = set(spec.donate_argnums if isinstance(
        spec.donate_argnums, (tuple, list)) else (spec.donate_argnums,))
    idx, out = 0, []
    for i, a in enumerate(spec.args):
        n = len(jax.tree_util.tree_leaves(a))
        if i in argnums:
            out.extend(range(idx, idx + n))
        idx += n
    return out


def _audit_donation(closed, spec: TraceSpec, lower_warnings) -> list[Finding]:
    findings = []

    def add(rule, severity, message, hint=""):
        findings.append(Finding(
            rule=rule, severity=severity, path=spec.name, line=None,
            message=message, hint=hint,
            suppressed=rule in spec.suppress))

    for w in lower_warnings:
        msg = str(w.message)
        if "donated" in msg.lower() or "donation" in msg.lower():
            add("donation-unused", "warn",
                "declared-donated buffer(s) could not be consumed: "
                + msg.split("See an explanation")[0].strip(),
                "a donated leaf needs a same-shape/dtype output to "
                "alias; drop the donation or return the updated value")

    donated = set(_donated_flat_indices(spec))
    if donated:
        invars = closed.jaxpr.invars
        outset = set(id(v) for v in closed.jaxpr.outvars)
        used = set()
        for eqn in closed.jaxpr.eqns:
            used.update(id(v) for v in eqn.invars)
        for i in donated:
            if i >= len(invars):
                continue
            v = invars[i]
            if id(v) in outset and id(v) in used:
                add("donation-read", "warn",
                    f"donated input #{i} is both read and returned "
                    "unchanged",
                    "XLA must copy to honor the aliasing, so the "
                    "donation buys nothing; return the derived value "
                    "or drop the donation for this argument")
    return findings


# ------------------------------------------------------------ entrypoint


@dataclasses.dataclass(frozen=True)
class TraceArtifacts:
    """One target's trace/lower/compile products, produced ONCE so the
    IR lint and the shard lint (analysis/shard_lint.py — placement
    census, resharding attribution) never pay a second backend compile
    for the same program.  ``compiled``/``hlo`` are None when only the
    jaxpr-level audits were requested."""

    closed: Any                 # the ClosedJaxpr (spec.fn traced)
    compiled: Any | None        # jax.stages.Compiled
    hlo: str | None             # post-SPMD HLO text of `compiled`
    lower_warnings: tuple = ()  # warnings captured during trace+lower


def trace_target(spec: TraceSpec, compile: bool = True) -> TraceArtifacts:
    """Trace (jaxpr), lower, and — unless ``compile=False`` — compile
    one target, capturing the lower-time diagnostics the donation audit
    reads.  Nothing executes."""
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        traced = spec.fn.trace(*spec.args)
        closed = traced.jaxpr
        # Lower the EXISTING trace (no second tracing pass) — cheap,
        # and it emits the donation diagnostics; only the census needs
        # the (expensive) backend compile.
        lowered = traced.lower()
        compiled = lowered.compile() if compile else None
    return TraceArtifacts(closed=closed, compiled=compiled,
                          hlo=compiled.as_text() if compiled else None,
                          lower_warnings=tuple(caught))


def lint_trace(spec: TraceSpec, compile_census: bool = True,
               artifacts: TraceArtifacts | None = None
               ) -> tuple[list[Finding], list[CollectiveOp]]:
    """Run every IR audit on one trace target.

    Returns (findings, collective census).  ``compile_census=False``
    skips the lower+compile (jaxpr-only audits — cheap when the census
    is not needed).  Pass ``artifacts`` (from :func:`trace_target`) to
    reuse an existing trace+compile.
    """
    art = artifacts if artifacts is not None else trace_target(
        spec, compile=compile_census)
    census: list[CollectiveOp] = (
        comm_census(art.hlo) if art.hlo is not None else [])
    findings = _audit_jaxpr(art.closed, spec)
    findings += _audit_donation(art.closed, spec, art.lower_warnings)
    return findings, census


# ------------------------------------------------------------ budgets


def census_to_budget(census: Sequence[CollectiveOp]) -> dict:
    return {"collectives": [c.as_json() for c in census],
            "wire_total": census_wire_total(census)}


def check_budget(name: str, census: Sequence[CollectiveOp],
                 budgets: dict) -> list[Finding]:
    """Compare one target's census against the checked-in budget.
    Any drift — new ops, missing ops, changed bytes — is a finding;
    re-record deliberate changes with ``graph_lint.py
    --update-budgets`` and review the JSON diff."""
    entry = budgets.get(name)
    if entry is None:
        return [Finding(
            rule="comm-budget", severity="error", path=name, line=None,
            message="no communication budget recorded for this target",
            hint="run scripts/graph_lint.py --update-budgets")]
    got = census_to_budget(census)
    want = {"collectives": entry.get("collectives", []),
            "wire_total": entry.get("wire_total")}
    if got == want:
        return []
    return [Finding(
        rule="comm-budget", severity="error", path=name, line=None,
        message=(f"collective census drifted from the budget: expected "
                 f"{want['wire_total']} wire bytes "
                 f"({len(want['collectives'])} op kinds), compiled to "
                 f"{got['wire_total']} wire bytes "
                 f"({len(got['collectives'])} op kinds)"),
        hint="if the change is intentional, re-record with "
             "scripts/graph_lint.py --update-budgets and review the "
             "scripts/comm_budget.json diff")]


def declared_zero_exchange(spec: TraceSpec, stage: int | None = None
                           ) -> dict:
    """Measure the ZeRO exchange the step DECLARES, from its traced
    jaxpr.  Per stage (``spec.zero_stage`` unless overridden):

    * stage 1 — ``rs_bytes``: the sharding-constraint reduce-scatters
      under the ``zero1/reduce_scatter`` scope; ``ag_bytes``: the
      explicit all-gathers (shard_map) under ``zero1/all_gather``;
    * stage 2 — ``rs_bytes``: the in-scan accumulator constraints
      under ``zero2/accum_scatter`` (one program occurrence covers the
      whole window — the scan body is one sub-jaxpr); ``ag_bytes``:
      the update all-gathers under ``zero2/all_gather``;
    * stage 3 — ``ag_bytes``: the gather-on-use constraints under
      ``zero3/param_gather``; ``rs_bytes``: the backward cotangent
      constraints under ``zero3/grad_scatter``.  NOTE the backward
      eqn's name stack reads ``transpose(jvp(zero3/param_gather))/
      zero3/grad_scatter`` — it contains BOTH scopes, so the scatter
      scope takes precedence.

    These are the real program's eqns (the hooks hand out the executed
    step), just read before GSPMD picks a backend-specific
    implementation."""
    stage = spec.zero_stage if stage is None else stage
    closed = spec.fn.trace(*spec.args).jaxpr
    out = {"rs_bytes": 0, "ag_bytes": 0}
    rs_scope = {1: "zero1/reduce_scatter", 2: "zero2/accum_scatter",
                3: "zero3/grad_scatter"}[stage]
    ag_scope = {1: "zero1/all_gather", 2: "zero2/all_gather",
                3: "zero3/param_gather"}[stage]
    ag_prim = "sharding_constraint" if stage == 3 else "shard_map"

    def nbytes(eqn):
        return sum(int(np.prod(v.aval.shape)) * v.aval.dtype.itemsize
                   for v in eqn.outvars if hasattr(v.aval, "shape"))

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            stack = str(getattr(eqn.source_info, "name_stack", ""))
            prim = eqn.primitive.name
            if prim == "sharding_constraint" and rs_scope in stack:
                out["rs_bytes"] += nbytes(eqn)
            elif prim == ag_prim and ag_scope in stack:
                out["ag_bytes"] += nbytes(eqn)
            for sub, _ in _subjaxprs(eqn):
                walk(sub)

    walk(closed.jaxpr)
    return out


def declared_zero1_exchange(spec: TraceSpec) -> dict:
    """Stage-1 spelling of :func:`declared_zero_exchange` (kept for
    older call sites)."""
    return declared_zero_exchange(spec, stage=1)


def check_zero1_parity(z1_spec: TraceSpec, dp_census) -> list[Finding]:
    """The ZeRO acceptance check (stages 1/2/3; the stage comes from
    ``spec.zero_stage``): the declared scatter/gather exchange must be
    PAD-FREE — each leg moves exactly the model's parameter bytes.

    With P = the model's parameter bytes, the check asserts (all
    measured, nothing assumed):

    1. the zero step declares scatter payload == P — i.e. the bucket
       layout added ZERO padding — and gather payload == P.  Per
       program occurrence: stage 1's post-scan RS and update AG, stage
       2's in-scan accumulator RS (the scan body is one occurrence
       covering the whole window — so the per-ROUND wire is
       ``window x RS(P) + AG(P)`` vs replicated DP's ``window x
       AR(P)``, stage 2's saving) and update AG, stage 3's
       gather-on-use AG and backward grad RS (no update gather at all);
    2. by the ring identity RS(P) + AG(P) carries exactly AR(P)'s
       wire bytes: ``2 (n-1)/n P`` per device — so stage 1's per-round
       exchange equals the replicated-DP gradient all-reduce volume,
       and stages 2/3 never exceed it;
    3. the DP partner's COMPILED all-reduces move >= P gradient bytes;
       moving more than P is reported as a warn finding (e.g. tied
       weights whose gradient contributions XLA reduces separately).

    (1)+(2) prove the headline claim; (3) pins it to the compiled DP
    program.  Compiled zero bytes are pinned separately by the census
    budget: XLA CPU implements the declared exchange hierarchically
    (subgroup all-reduces + permutes), a backend artifact the budget
    tracks but parity must not depend on.
    """
    findings = []
    P = z1_spec.params_bytes
    stage = z1_spec.zero_stage

    def add(rule, severity, message, hint=""):
        findings.append(Finding(
            rule=rule, severity=severity, path=z1_spec.name, line=None,
            message=message, hint=hint,
            suppressed=rule in z1_spec.suppress))

    if not P:
        add("zero1-parity", "error",
            "zero parity target carries no params_bytes reference",
            "the traced_for_analysis hook must fill params_bytes")
        return findings
    decl = declared_zero_exchange(z1_spec)
    if decl["rs_bytes"] != P or decl["ag_bytes"] != P:
        add("zero1-parity", "error",
            f"declared stage-{stage} exchange scatter="
            f"{decl['rs_bytes']} / gather={decl['ag_bytes']} bytes != "
            f"parameter bytes {P} — the exchange no longer carries "
            "exactly the volume the proof pins",
            "nonzero bucket padding (a leaf size stopped dividing by "
            "the data axis) or a missing zero named scope; inspect "
            "collectives.Zero1Layout for this parameter tree")
    # The DP partner's compiled gradient all-reduce: every AR big
    # enough to be a gradient leaf (scalars like the loss mean are
    # bookkeeping, not exchange).
    min_leaf = max(32, min((c.payload_bytes for c in dp_census
                            if c.op == "all-reduce"), default=0))
    dp_grad = sum(c.payload_bytes * c.count for c in dp_census
                  if c.op == "all-reduce" and c.payload_bytes >= min_leaf)
    if dp_grad < P:
        add("zero1-parity", "error",
            f"DP partner compiles only {dp_grad} gradient all-reduce "
            f"bytes for {P} parameter bytes — the reference volume is "
            "not what zero1 replaces",
            "the gradient-AR classifier (payload >= smallest leaf) "
            "may need tuning for this model, or DP stopped "
            "all-reducing some leaves")
    elif dp_grad > P:
        # Promoted info -> warn (PR 4): the one known instance — the
        # tied embedding's two gradient contributions all-reduced
        # separately in replicated-DP LMTrainer — is fixed (the
        # shard_map-local gradient construction sums them before the
        # exchange, trainers/lm.py), so any reappearance is a
        # regression and gates CI.
        add("comm-redundant-ar", "warn",
            f"replicated-DP compiles {dp_grad} all-reduce bytes for "
            f"{P} parameter bytes ({dp_grad - P} redundant)",
            "usually tied weights whose gradient contributions XLA "
            "reduces separately instead of summing locally first "
            "(sum them before the exchange, as LMTrainer's "
            "_dp_local_value_and_grad does); zero1's declared "
            "exchange does not inherit this")
    return findings


def load_budgets(path: str) -> dict:
    with open(path) as f:
        return json.load(f)["targets"]


def save_budgets(path: str, budgets: dict, device_count: int | None = None
                 ) -> None:
    doc = {
        "comment": "per-step collective census (payload/wire bytes per "
                   "device, ring model) on the 8-device CPU mesh; "
                   "re-record with scripts/graph_lint.py "
                   "--update-budgets and review the diff",
        "device_count": (device_count if device_count is not None
                         else jax.device_count()),
        "targets": budgets,
    }
    with open(path, "w") as f:
        json.dump(doc, f, indent=1, sort_keys=True)
        f.write("\n")


__all__ = ["TraceSpec", "CollectiveOp", "TraceArtifacts",
           "trace_target", "comm_census", "lint_trace",
           "census_wire_total", "census_to_budget", "check_budget",
           "declared_zero_exchange", "declared_zero1_exchange",
           "check_zero1_parity", "load_budgets", "save_budgets"]

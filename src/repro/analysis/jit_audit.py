"""Static auditor for the serving engine's jitted decode hot path.

The engine's throughput story depends on the tick loop staying
device-resident: one compile per bucket shape, no host round-trips
inside jitted functions, donated slot state actually donated and never
read after the call.  Nothing in the type system enforces any of that —
a PR can reintroduce a per-row host sync or a retrace-per-batch-shape
and every test still passes, just slower.  This module makes those
regressions *diagnosable before merge*:

``audit_engine(engine)`` drives a scripted workload through the
engine's real ``generate`` path with shape-recording proxies wrapped
around every jitted target (``_insert``, ``_decode``, and the
``_prefill`` / ``_prefill_from`` bucket ladders), then checks:

  JIT001  host callback primitives (``debug_callback``,
          ``pure_callback``, ``io_callback``) anywhere in a target's
          jaxpr — each one is a device->host round trip per tick
  JIT002  XLA reporting a donated buffer as unusable at compile time
          (a silent defensive copy; platform-unimplemented donation,
          e.g. CPU, is not flagged)
  JIT003  a call site of a donating jitted function whose donated
          argument is not rebound from the call result (AST check over
          the engine source — reading the old binding after the call
          is a use-after-free on accelerators)
  JIT004  weak-typed python scalars in a target's signature (dtype
          promotion surprises; pass ``jnp.int32(x)``-style arrays)
  JIT005  strong f32 scalar literals promoting bf16/f16 operands
  JIT006  retrace hazard: a target compiled more entries than the
          distinct input shape/dtype signatures observed — something
          besides shapes (a changing static, a weak-type flip) is
          forking the jit cache
  JIT007/8/9  per-decode-step FLOP / memory-traffic / collective
          budgets, extracted from the compiled step via
          ``launch/hlo_analysis.py``

The checks are static where possible (jaxprs, AST, compile artifacts);
the scripted workload exists only to collect real example signatures
and exercise the jit caches whose sizes JIT006 reads.
"""
from __future__ import annotations

import ast
import inspect
import warnings
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.extend.core as jex_core

from repro.analysis.diagnostics import Diagnostic

CALLBACK_PRIMS = ("debug_callback", "debug_print", "pure_callback",
                  "io_callback",
                  "callback", "host_callback_call", "outside_call")

LOW_PRECISION = ("bfloat16", "float16")


# ---------------------------------------------------------------------------
# jaxpr plumbing
# ---------------------------------------------------------------------------

def _subjaxprs(v):
    if isinstance(v, jex_core.ClosedJaxpr):
        yield v.jaxpr
    elif isinstance(v, jex_core.Jaxpr):
        yield v
    elif isinstance(v, (list, tuple)):
        for x in v:
            yield from _subjaxprs(x)


def iter_eqns(jaxpr):
    """Every equation of a (closed) jaxpr, recursing into sub-jaxprs
    (pjit bodies, scan/while/cond branches, vmapped calls)."""
    if isinstance(jaxpr, jex_core.ClosedJaxpr):
        jaxpr = jaxpr.jaxpr
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in _subjaxprs(v):
                yield from iter_eqns(sub)


def jaxpr_of(fn: Callable, args: Tuple, kwargs: Dict):
    """The function's closed jaxpr for the example signature, or None
    when tracing is impossible (e.g. the example was never recorded)."""
    try:
        return jax.make_jaxpr(fn)(*args, **kwargs)
    except Exception:
        return None


# ---------------------------------------------------------------------------
# signature recording — the retrace oracle
# ---------------------------------------------------------------------------

def _leaf_sig(x) -> Tuple:
    if hasattr(x, "shape") and hasattr(x, "dtype"):
        return ("arr", tuple(x.shape), str(x.dtype))
    # python scalars: jit abstracts them by TYPE (weak scalar avals),
    # so the signature deliberately excludes the value — a cache that
    # still forks per call has a non-shape retrace cause
    return ("py", type(x).__name__)


def _abstractify(x):
    if hasattr(x, "shape") and hasattr(x, "dtype"):
        return jax.ShapeDtypeStruct(tuple(x.shape), x.dtype)
    return x


def call_signature(args: Tuple, kwargs: Dict) -> Tuple:
    leaves, treedef = jax.tree.flatten((args, kwargs))
    return (str(treedef), tuple(_leaf_sig(leaf) for leaf in leaves))


class JitCallRecorder:
    """Transparent proxy around a jitted callable: records the distinct
    abstract signatures flowing through it (and one spec-level example
    per run) without perturbing the underlying jit cache."""

    def __init__(self, name: str, fn: Callable):
        self.name = name
        self.fn = fn
        self.calls = 0
        self.signatures: set = set()
        self.example: Optional[Tuple[Tuple, Dict]] = None

    def __call__(self, *args, **kwargs):
        # record BEFORE the call: donated operands are deleted after
        self.signatures.add(call_signature(args, kwargs))
        if self.example is None:
            self.example = (jax.tree.map(_abstractify, args),
                            jax.tree.map(_abstractify, kwargs))
        self.calls += 1
        return self.fn(*args, **kwargs)

    def cache_size(self) -> Optional[int]:
        try:
            return int(self.fn._cache_size())
        except Exception:
            return None


# ---------------------------------------------------------------------------
# individual checks
# ---------------------------------------------------------------------------

def audit_callbacks(name: str, closed) -> List[Diagnostic]:
    out = []
    for eqn in iter_eqns(closed):
        if eqn.primitive.name in CALLBACK_PRIMS:
            out.append(Diagnostic(
                "JIT001",
                f"{eqn.primitive.name} primitive inside the jitted hot "
                "path — a device->host round trip on every invocation",
                f"engine.{name}",
                hint="remove the callback (or debug print) from the "
                     "tick loop; stage debugging through returned "
                     "arrays instead"))
    return out


def audit_weak_args(name: str, closed) -> List[Diagnostic]:
    out = []
    for i, v in enumerate(closed.jaxpr.invars):
        aval = v.aval
        if not getattr(aval, "weak_type", False):
            continue
        dt = str(getattr(aval, "dtype", ""))
        sev = "warning" if dt.startswith("float") else "info"
        out.append(Diagnostic(
            "JIT004",
            f"argument {i} is a weak-typed python scalar ({dt}) — "
            "promotion rules differ from committed dtypes",
            f"engine.{name}", severity=sev,
            hint="pass jnp.asarray(x, dtype) / jnp.int32(x) so the "
                 "operand dtype is explicit"))
    return out


def audit_promotions(name: str, closed) -> List[Diagnostic]:
    """Strong f32 scalar literals silently widening bf16/f16 math."""
    out = []
    for eqn in iter_eqns(closed):
        lits = [v for v in eqn.invars if isinstance(v, jex_core.Literal)]
        arrs = [v for v in eqn.invars
                if not isinstance(v, jex_core.Literal)]
        if not (lits and arrs and eqn.outvars):
            continue
        strong_f32_lit = any(
            str(getattr(v.aval, "dtype", "")) == "float32"
            and not getattr(v.aval, "weak_type", False)
            and getattr(v.aval, "ndim", 1) == 0 for v in lits)
        low_arr = any(str(getattr(v.aval, "dtype", "")) in LOW_PRECISION
                      for v in arrs)
        promoted = any(str(getattr(v.aval, "dtype", "")) == "float32"
                       for v in eqn.outvars)
        if strong_f32_lit and low_arr and promoted:
            out.append(Diagnostic(
                "JIT005",
                f"{eqn.primitive.name}: strong f32 scalar constant "
                "promotes a low-precision operand to f32",
                f"engine.{name}", severity="warning",
                hint="use a weak python float or cast the constant to "
                     "the operand dtype"))
    return out


def audit_retrace(rec: JitCallRecorder) -> List[Diagnostic]:
    cache = rec.cache_size()
    if cache is None or not rec.calls:
        return []
    sigs = len(rec.signatures)
    if cache > sigs:
        return [Diagnostic(
            "JIT006",
            f"{cache} compiled entries for {sigs} distinct input "
            f"signature(s) over {rec.calls} call(s) — the jit cache is "
            "forking on something other than shapes/dtypes",
            f"engine.{rec.name}",
            hint="look for changing static argnums, python-scalar "
                 "dtype flips, or closures rebuilt per call")]
    return []


def audit_donation_compile(name: str, fn, example) -> List[Diagnostic]:
    """Compile the target and surface XLA's donated-buffer-unusable
    warnings (platform-unimplemented donation is not a finding)."""
    if example is None:
        return []
    args, kwargs = example
    out = []
    try:
        with warnings.catch_warnings(record=True) as caught:
            warnings.simplefilter("always")
            fn.lower(*args, **kwargs).compile()
    except Exception:
        return []
    for w in caught:
        msg = str(w.message)
        if "donated" not in msg.lower():
            continue
        if "not implemented" in msg.lower():
            continue          # platform limitation, not a code defect
        out.append(Diagnostic(
            "JIT002", f"XLA: {msg.splitlines()[0][:160]}",
            f"engine.{name}",
            hint="donated operands must match an output's "
                 "shape/dtype for buffer reuse"))
    return out


def audit_donation_sites(source: str, donations: Dict[str, Tuple[int, ...]],
                         location: str) -> List[Diagnostic]:
    """AST check: every call of a donating jitted function must rebind
    its donated argument from the call's result in the same statement.
    Reading the old binding after the call is a use-after-free on
    accelerators (and a silent copy on others)."""
    out = []
    tree = ast.parse(source)
    parents: Dict[ast.AST, ast.AST] = {}
    for parent in ast.walk(tree):
        for child in ast.iter_child_nodes(parent):
            parents[child] = parent
    for call in ast.walk(tree):
        if not isinstance(call, ast.Call):
            continue
        fname = None
        if isinstance(call.func, ast.Attribute):
            fname = call.func.attr
        elif isinstance(call.func, ast.Name):
            fname = call.func.id
        if fname not in donations:
            continue
        stmt: ast.AST = call
        while stmt in parents and not isinstance(stmt, ast.stmt):
            stmt = parents[stmt]
        targets: List[ast.AST] = []
        if isinstance(stmt, ast.Assign):
            for t in stmt.targets:
                targets.extend(t.elts if isinstance(
                    t, (ast.Tuple, ast.List)) else [t])
        elif isinstance(stmt, (ast.AnnAssign, ast.AugAssign)):
            targets = [stmt.target]
        # unparse, not ast.dump: the donated arg is a Load and the
        # assignment target a Store — textual identity is the question
        target_dumps = {ast.unparse(t) for t in targets}
        for pos in donations[fname]:
            if pos >= len(call.args):
                continue
            arg = call.args[pos]
            if not isinstance(arg, (ast.Name, ast.Attribute)):
                continue      # temporaries cannot be read again
            if ast.unparse(arg) not in target_dumps:
                out.append(Diagnostic(
                    "JIT003",
                    f"{fname}() donates argument {pos} "
                    f"({ast.unparse(arg)}) but the call site does not "
                    "rebind it from the result",
                    f"{location}:{call.lineno}",
                    hint="write `x = fn(x, ...)` (or unpack into it) "
                         "so the donated binding can never be read "
                         "after the transfer"))
    return out


# ---------------------------------------------------------------------------
# decode-step budgets (reuses launch/hlo_analysis roofline extraction)
# ---------------------------------------------------------------------------

def audit_decode_budget(engine, rec: JitCallRecorder, *,
                        flop_factor: float = 4.0,
                        bytes_factor: float = 16.0
                        ) -> Tuple[List[Diagnostic], Optional[Dict]]:
    """Compile the decode step and check its extracted FLOP/byte/
    collective terms against analytic budgets: ~2·N_active per token
    for compute, params + 2x slot state for traffic, zero collectives
    single-device.

    ``bytes_factor`` is deliberately loose: ``cost_analysis`` counts
    every buffer access (a clean tiny-model step measures ~9x its
    analytic HBM traffic on CPU), while the regression this catches —
    re-touching the whole cache per emitted token, or a prefill inside
    the step — multiplies traffic by O(seq_len)."""
    from repro.configs.base import ShapeSpec
    from repro.launch import hlo_analysis as HLO
    if rec.example is None:
        return [], None
    args, kwargs = rec.example
    try:
        compiled = rec.fn.lower(*args, **kwargs).compile()
        roof = HLO.analyze(compiled, chips=1)
    except Exception:
        return [], None
    spec = ShapeSpec("audit_decode", seq_len=engine.max_len,
                     global_batch=engine.slots, kind="decode")
    expected_flops = HLO.model_flops(engine.cfg, spec)
    param_bytes = sum(x.nbytes for x in jax.tree.leaves(engine.params))
    state_bytes = sum(x.nbytes
                     for x in jax.tree.leaves(engine._slot_state or {}))
    expected_bytes = param_bytes + 2 * state_bytes
    detail = {"flops": roof.flops, "expected_flops": expected_flops,
              "bytes": roof.bytes_accessed,
              "expected_bytes": expected_bytes,
              "coll_bytes": roof.coll_bytes,
              "coll_detail": roof.coll_detail}
    diags = []
    if expected_flops and roof.flops > flop_factor * expected_flops:
        diags.append(Diagnostic(
            "JIT007",
            f"decode step costs {roof.flops:.3g} FLOPs vs "
            f"~{expected_flops:.3g} for 2·N_active·slots "
            f"(>{flop_factor:g}x budget)", "engine._decode",
            severity="warning",
            hint="look for recomputation over the whole cache or an "
                 "accidental prefill inside the step"))
    if expected_bytes and \
            roof.bytes_accessed > bytes_factor * expected_bytes:
        diags.append(Diagnostic(
            "JIT008",
            f"decode step moves {roof.bytes_accessed:.3g} bytes vs "
            f"~{expected_bytes:.3g} for params + 2x slot state "
            f"(>{bytes_factor:g}x budget)", "engine._decode",
            severity="warning",
            hint="the step should read params once and touch slot "
                 "state, nothing larger"))
    if roof.coll_bytes > 0 and engine.mesh is None:
        diags.append(Diagnostic(
            "JIT009",
            f"decode step contains collectives "
            f"({roof.coll_detail}) on a single-device engine",
            "engine._decode"))
    return diags, detail


# ---------------------------------------------------------------------------
# the engine audit
# ---------------------------------------------------------------------------

@dataclass
class AuditReport:
    diagnostics: List[Diagnostic]
    cache_stats: Dict[str, Dict[str, int]] = field(default_factory=dict)
    budget: Optional[Dict] = None

    def to_dict(self) -> Dict:
        return {"diagnostics": [d.to_dict() for d in self.diagnostics],
                "cache_stats": self.cache_stats, "budget": self.budget}


def default_workload(engine) -> List[str]:
    """Deterministic prompts exercising every bucket of the engine's
    ladder plus partial-batch admission (so retrace detection sees the
    admission widths real traffic produces)."""
    prompts = [f"row {i} value v{i}" for i in range(2 * engine.slots + 1)]
    if len(engine.buckets) > 1:
        pad = "x" * (engine.buckets[0] + 2)
        prompts += [f"{pad} long row {i}" for i in range(2)]
    return prompts


def _install(engine) -> Dict[str, JitCallRecorder]:
    recs = {n: JitCallRecorder(n, getattr(engine, n))
            for n in ("_insert", "_decode", "_next_tok")}
    for n, rec in recs.items():
        setattr(engine, n, rec)
    if getattr(engine, "_seed", None) is not None:
        recs["_seed"] = JitCallRecorder("_seed", engine._seed)
        engine._seed = recs["_seed"]
    for b, fn in list(engine._prefill.items()):
        r = JitCallRecorder(f"_prefill[{b}]", fn)
        recs[r.name] = r
        engine._prefill[b] = r
    for b, fn in list(engine._prefill_from.items()):
        r = JitCallRecorder(f"_prefill_from[{b}]", fn)
        recs[r.name] = r
        engine._prefill_from[b] = r
    return recs


def _restore(engine, recs: Dict[str, JitCallRecorder]) -> None:
    for n in ("_insert", "_decode", "_next_tok"):
        setattr(engine, n, recs[n].fn)
    if "_seed" in recs:
        engine._seed = recs["_seed"].fn
    for b in list(engine._prefill):
        engine._prefill[b] = recs[f"_prefill[{b}]"].fn
    for b in list(engine._prefill_from):
        engine._prefill_from[b] = recs[f"_prefill_from[{b}]"].fn


# donated positions of the engine's jitted targets (matches the
# donate_argnums in Engine.__init__); the AST check audits every call
# site of these names in the engine source
ENGINE_DONATIONS: Dict[str, Tuple[int, ...]] = {
    "_insert": (0,),     # slot_state
    "_decode": (1,),     # slot_state
    "_seed": (0,),       # slot_state (paged prefix-block seeding)
}


def audit_engine(engine, prompts: Optional[List[str]] = None, *,
                 max_new: int = 4, flop_factor: float = 4.0,
                 bytes_factor: float = 16.0,
                 source: Optional[str] = None) -> AuditReport:
    """Run the full hot-path audit against a live engine.

    Drives ``prompts`` (default: a bucket-covering scripted workload)
    through ``generate`` — plus a prefix-seeded pass when the engine
    has a prefix cache, so the ``_prefill_from`` ladder is exercised —
    then applies every static check to the recorded targets.
    ``source`` overrides the audited call-site source text (tests use
    this to prove JIT003 fires)."""
    if prompts is None:
        prompts = default_workload(engine)
    recs = _install(engine)
    try:
        engine.generate(list(prompts), max_new=max_new)
        if engine.prefix_cache is not None:
            tpl = "audit template: "
            engine.generate([f"{tpl}row {i}" for i in range(engine.slots)],
                            max_new=max_new, prefix=tpl)
    finally:
        _restore(engine, recs)

    diags: List[Diagnostic] = []
    cache_stats: Dict[str, Dict[str, int]] = {}
    for name, rec in recs.items():
        if not rec.calls:
            continue
        cache_stats[name] = {"calls": rec.calls,
                             "signatures": len(rec.signatures),
                             "compiles": rec.cache_size() or 0}
        diags.extend(audit_retrace(rec))
        closed = (jaxpr_of(rec.fn, *rec.example)
                  if rec.example is not None else None)
        if closed is not None:
            diags.extend(audit_callbacks(name, closed))
            diags.extend(audit_weak_args(name, closed))
            diags.extend(audit_promotions(name, closed))
        diags.extend(audit_donation_compile(name, rec.fn, rec.example))

    if source is None:
        from repro.serving import engine as engine_module
        source = inspect.getsource(engine_module)
    diags.extend(audit_donation_sites(source, ENGINE_DONATIONS,
                                      "serving/engine.py"))
    budget_diags, budget = audit_decode_budget(
        engine, recs["_decode"], flop_factor=flop_factor,
        bytes_factor=bytes_factor)
    diags.extend(budget_diags)
    return AuditReport(diags, cache_stats, budget)

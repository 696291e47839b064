"""Static program auditor: invariant checks over jaxprs and lowerings.

Every hot program this repo dispatches — the fused train step, the
serving prefill buckets, the decode step, the dispatched forward — obeys
invariants the runtime tests can only catch *after* the damage:
trace-time constants bloat HBM at first dispatch, a missed donation doubles the arena per step, an f32 upcast halves MXU
throughput silently, a host callback turns a 2 ms step into a 50 ms
round trip, and a python scalar re-derived from a per-call shape breaks
the zero-recompile contract the whole serving tier is built on. All of
those are visible in the **jaxpr**, before anything runs.

``audit_entrypoints`` takes entry-point *specs* — name, (jitted) fn,
example args, the effective ``donate_argnums`` — traces each with
``jax.make_jaxpr`` (no execution, no compile) and emits findings:

- ``baked-constant``  (P1) — a trace-time constant bigger than the
  threshold is closed over by the program (captured weights, the PR 2
  class of accidental closure capture); it lives in HBM per-executable.
- ``donation-miss``   (P1) — an input whose aval matches an output but
  is not donated, on a program that *does* donate (``donate_expected``);
  cross-checked against the compiled ``memory_analysis`` aliasing when
  a compile is allowed, so an alias XLA already made is not re-flagged.
- ``f32-drift``       (P1) — a dot/conv operand is f32 inside a program
  whose floating inputs are bf16/fp8: an accidental upcast *before* the
  matmul (legit f32 accumulation via preferred_element_type keeps bf16
  operands and is not flagged).
- ``host-callback``   (P1) / ``implicit-transfer`` (P2) — pure/io/debug
  callbacks or device_put equations inside a hot program.
- ``weak-shape``      (P2) — with a ``shape_probe`` arg set: a scalar
  literal in the program changes when only input *shapes* change, i.e.
  a python value re-derived from per-call shapes that will force a
  recompile per shape (the zero-recompile invariant killer).

The module imports jax lazily so ``accelerate_tpu.analysis`` stays in
the declared jax-free set; only actually *running* a program audit needs
an accelerator stack.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Optional

from .findings import Finding

# thresholds: a baked constant below 1 MiB is noise (iota tables, masks);
# a donation miss below 64 KiB is a scalar/bookkeeping vector, not an
# arena. Both overridable per audit call.
CONST_BYTES_THRESHOLD = 1 << 20
DONATION_BYTES_THRESHOLD = 1 << 16

_CALLBACK_PRIMS = (
    "pure_callback", "io_callback", "debug_callback", "host_callback",
    "outside_call", "debug_print",
)
_LOW_PRECISION = ("bfloat16", "float8_e4m3fn", "float8_e5m2", "float16")
_MATMUL_PRIMS = ("dot_general", "conv_general_dilated", "ragged_dot")


@dataclass
class EntrypointSpec:
    """One auditable program. ``fn`` may be jit-wrapped or plain;
    ``args``/``kwargs`` are example inputs (traced, never executed).
    ``donate`` is the *effective* donate_argnums; ``donate_expected``
    False means the caller deliberately runs without donation (the CPU
    sim keeps it off) and donation checks are skipped rather than
    reported as misses. ``shape_probe`` is a second arg tuple with the
    per-call-varying dims bumped, enabling the weak-shape check.
    ``compile_check`` allows a real ``.lower().compile()`` for the
    memory_analysis aliasing cross-check (costs a compile — off by
    default so audits never touch a backend compiler unasked)."""

    name: str
    fn: object
    args: tuple = ()
    kwargs: dict = field(default_factory=dict)
    donate: tuple = ()
    donate_expected: Optional[bool] = None
    compute_dtype: Optional[str] = None
    shape_probe: Optional[tuple] = None
    compile_check: bool = False

    @classmethod
    def normalize(cls, spec) -> "EntrypointSpec":
        if isinstance(spec, cls):
            return spec
        return cls(**dict(spec))


# -- jaxpr plumbing ---------------------------------------------------------


def _closed_jaxprs(closed):
    """The top-level ClosedJaxpr plus every nested one (pjit bodies, scan
    carries, cond branches, custom-derivative calls), depth-first in
    deterministic order."""
    from jax.extend import core

    out = []

    def walk(cj):
        out.append(cj)
        for eqn in cj.jaxpr.eqns:
            for val in eqn.params.values():
                stack = [val]
                while stack:
                    v = stack.pop()
                    if isinstance(v, core.ClosedJaxpr):
                        walk(v)
                    elif isinstance(v, core.Jaxpr):
                        walk(core.ClosedJaxpr(v, ()))
                    elif isinstance(v, (tuple, list)):
                        stack.extend(v)
    walk(closed)
    return out


def _all_eqns(closed):
    for cj in _closed_jaxprs(closed):
        for eqn in cj.jaxpr.eqns:
            yield eqn


def _aval_key(aval):
    return (tuple(getattr(aval, "shape", ())), str(getattr(aval, "dtype", "?")))


def _aval_str(aval) -> str:
    shape, dtype = tuple(getattr(aval, "shape", ())), getattr(aval, "dtype", "?")
    return f"{dtype}[{','.join(str(d) for d in shape)}]"


def _nbytes(aval) -> int:
    import numpy as np

    size = 1
    for d in getattr(aval, "shape", ()):
        size *= int(d)
    try:
        return size * np.dtype(aval.dtype).itemsize
    except Exception:
        return size


def _trace(fn, args, kwargs):
    import jax

    return jax.make_jaxpr(fn)(*args, **kwargs)


def _leaf_counts(args) -> list:
    import jax

    return [len(jax.tree_util.tree_leaves(a)) for a in args]


# -- the checks -------------------------------------------------------------


def _check_baked_constants(spec, closed, threshold) -> list:
    findings = []
    seen: dict = {}
    for cj in _closed_jaxprs(closed):
        for const in cj.consts:
            nbytes = int(getattr(const, "nbytes", 0) or 0)
            if nbytes < threshold:
                continue
            key = f"{getattr(const, 'dtype', '?')}[{','.join(str(d) for d in getattr(const, 'shape', ()))}]"
            if key in seen:
                seen[key]["count"] += 1
                seen[key]["bytes"] += nbytes
            else:
                seen[key] = {"count": 1, "bytes": nbytes}
    for key, info in sorted(seen.items()):
        findings.append(Finding(
            check="baked-constant", severity="P1", target=spec.name,
            anchor=key,
            message=f"{spec.name} bakes a {info['bytes'] / 1e6:.1f} MB "
                    f"trace-time constant ({key} x{info['count']}) into the "
                    "program — a closed-over concrete array (weights?) "
                    "duplicated into executable HBM; pass it as an argument",
            detail={"bytes": info["bytes"], "count": info["count"]},
        ))
    return findings


def _compiled_alias_bytes(spec) -> Optional[int]:
    """``memory_analysis().alias_size_in_bytes`` of the compiled program
    (None when compiling is not allowed / not supported)."""
    if not spec.compile_check:
        return None
    try:
        lowered = spec.fn.lower(*spec.args, **spec.kwargs)
        ma = lowered.compile().memory_analysis()
        v = getattr(ma, "alias_size_in_bytes", None)
        return int(v) if isinstance(v, (int, float)) else None
    except Exception:
        return None


def _check_donation(spec, closed, threshold) -> list:
    donate = tuple(spec.donate or ())
    expected = spec.donate_expected
    if expected is None:
        expected = bool(donate)
    if not expected:
        return []  # donation deliberately off (CPU sim) — policy, not a miss
    in_avals, out_avals = list(closed.in_avals), list(closed.out_avals)
    counts = _leaf_counts(spec.args)
    # output-aval capacity, donated args claiming their matches first so a
    # correctly-donated arena does not leave phantom capacity behind
    capacity: dict = {}
    for aval in out_avals:
        key = _aval_key(aval)
        capacity[key] = capacity.get(key, 0) + 1
    spans, pos = [], 0
    for n in counts:
        spans.append((pos, pos + n))
        pos += n
    for i in donate:
        if i < len(spans):
            lo, hi = spans[i]
            for aval in in_avals[lo:hi]:
                key = _aval_key(aval)
                if capacity.get(key, 0) > 0:
                    capacity[key] -= 1
    findings = []
    alias_checked = False
    for i, (lo, hi) in enumerate(spans):
        if i in donate:
            continue
        matched_bytes, matched = 0, []
        for aval in in_avals[lo:hi]:
            key = _aval_key(aval)
            if capacity.get(key, 0) > 0:
                capacity[key] -= 1
                matched_bytes += _nbytes(aval)
                matched.append(_aval_str(aval))
        if matched_bytes < threshold:
            continue
        if not alias_checked:
            alias_checked = True
            alias_bytes = _compiled_alias_bytes(spec)
            donated_bytes = sum(
                _nbytes(a)
                for j in donate if j < len(spans)
                for a in in_avals[spans[j][0]:spans[j][1]]
            )
            if alias_bytes is not None and alias_bytes >= donated_bytes + matched_bytes:
                # XLA already aliases these buffers (input-output aliasing
                # beyond donate_argnums) — nothing to win
                return []
        findings.append(Finding(
            check="donation-miss", severity="P1", target=spec.name,
            anchor=f"arg{i}",
            message=f"{spec.name} donates {list(donate)} but arg {i} "
                    f"({matched_bytes / 1e6:.2f} MB: {', '.join(matched[:4])}"
                    f"{'...' if len(matched) > 4 else ''}) aval-matches "
                    "undonated outputs — the update allocates a second copy "
                    "per call instead of writing in place; donate it (and "
                    "make sure restored checkpoints re-own their buffers "
                    "before a donated executable consumes them)",
            detail={"bytes": matched_bytes, "arg": i, "avals": matched[:8]},
        ))
    return findings


def _program_float_dtype(spec, closed) -> Optional[str]:
    if spec.compute_dtype:
        return str(spec.compute_dtype)
    counts: dict = {}
    for aval in closed.in_avals:
        dt = str(getattr(aval, "dtype", ""))
        if dt.startswith(("float", "bfloat")):
            counts[dt] = counts.get(dt, 0) + 1
    if not counts:
        return None
    return max(counts, key=counts.get)


def _check_dtype_drift(spec, closed) -> list:
    prog_dtype = _program_float_dtype(spec, closed)
    if prog_dtype not in _LOW_PRECISION:
        return []
    findings, seen = [], set()
    for eqn in _all_eqns(closed):
        prim = eqn.primitive.name
        if prim not in _MATMUL_PRIMS:
            continue
        bad = [
            _aval_str(v.aval) for v in eqn.invars
            if str(getattr(v.aval, "dtype", "")) == "float32"
            and getattr(v.aval, "shape", ()) != ()
        ]
        if not bad:
            continue
        anchor = f"{prim}:{bad[0]}"
        if anchor in seen:
            continue
        seen.add(anchor)
        findings.append(Finding(
            check="f32-drift", severity="P1", target=spec.name,
            anchor=anchor,
            message=f"{spec.name} is a {prog_dtype} program but feeds "
                    f"f32 operands ({', '.join(bad[:3])}) into {prim} — an "
                    "upcast before the matmul runs it at half MXU rate; "
                    "accumulate in f32 via preferred_element_type and keep "
                    "operands low-precision",
            detail={"prim": prim, "operands": bad[:6]},
        ))
    return findings


def _check_host_callbacks(spec, closed) -> list:
    findings, seen = [], set()
    for eqn in _all_eqns(closed):
        prim = eqn.primitive.name
        check = None
        if prim in _CALLBACK_PRIMS or "callback" in prim:
            check, sev, what = "host-callback", "P1", "a host callback"
        elif prim == "device_put":
            check, sev, what = "implicit-transfer", "P2", "an implicit transfer"
        if check is None or (check, prim) in seen:
            continue
        seen.add((check, prim))
        findings.append(Finding(
            check=check, severity=sev, target=spec.name, anchor=prim,
            message=f"{spec.name} contains {what} ({prim}) — every dispatch "
                    "pays a host round trip inside the hot program; move it "
                    "out of the jitted body (telemetry hooks belong on the "
                    "host side of the dispatch)",
            detail={"prim": prim},
        ))
    return findings


def _scalar_literals(closed) -> list:
    """Ordered (eqn_index, prim, position, value) scalar int/float
    Literal operands across all nested jaxprs — the values a python
    computation baked into the trace."""
    from jax.extend import core

    out = []
    for i, eqn in enumerate(_all_eqns(closed)):
        for pos, v in enumerate(eqn.invars):
            if isinstance(v, core.Literal):
                val = v.val
                if getattr(val, "shape", ()) == ():
                    try:
                        out.append((i, eqn.primitive.name, pos, float(val)))
                    except (TypeError, ValueError):
                        pass
    return out


def _input_dims(args) -> set:
    import jax

    dims = set()
    for leaf in jax.tree_util.tree_leaves(args):
        for d in getattr(leaf, "shape", ()):
            dims.add(float(d))
    return dims


def _check_weak_shape(spec) -> list:
    if spec.shape_probe is None:
        return []
    base = _trace(spec.fn, spec.args, spec.kwargs)
    probe = _trace(spec.fn, spec.shape_probe, spec.kwargs)
    lits_a, lits_b = _scalar_literals(base), _scalar_literals(probe)
    if len(lits_a) != len(lits_b) or [x[:3] for x in lits_a] != [x[:3] for x in lits_b]:
        return [Finding(
            check="weak-shape", severity="P2", target=spec.name,
            anchor="trace-structure",
            message=f"{spec.name}'s trace STRUCTURE changes with input "
                    "shapes (different equation/literal layout between the "
                    "base and probe trace) — python control flow over "
                    "per-call shapes; every new shape is a new program",
        )]
    dims_a, dims_b = _input_dims(spec.args), _input_dims(spec.shape_probe)
    findings, seen = [], set()
    for (i, prim, pos, va), (_, _, _, vb) in zip(lits_a, lits_b):
        if va == vb:
            continue
        if va in dims_a and vb in dims_b:
            anchor = f"{prim}@{pos}"
            if anchor in seen:
                continue
            seen.add(anchor)
            findings.append(Finding(
                check="weak-shape", severity="P2", target=spec.name,
                anchor=anchor,
                message=f"{spec.name} bakes a python scalar re-derived from "
                        f"a per-call array shape ({va:g} -> {vb:g} when the "
                        f"shape changes) into {prim} — the zero-recompile "
                        "invariant breaks on the first differently-shaped "
                        "call; carry the value as a traced operand instead",
                detail={"prim": prim, "base": va, "probe": vb},
            ))
    return findings


# -- the audit entry points -------------------------------------------------


def audit_program(spec, *, const_bytes=CONST_BYTES_THRESHOLD,
                  donation_bytes=DONATION_BYTES_THRESHOLD) -> list:
    """All checks over one entry-point spec. Tracing only — the program
    never executes and nothing compiles unless ``compile_check`` asks
    for the aliasing cross-check."""
    spec = EntrypointSpec.normalize(spec)
    closed = _trace(spec.fn, spec.args, spec.kwargs)
    findings = []
    findings += _check_baked_constants(spec, closed, const_bytes)
    findings += _check_donation(spec, closed, donation_bytes)
    findings += _check_dtype_drift(spec, closed)
    findings += _check_host_callbacks(spec, closed)
    findings += _check_weak_shape(spec)
    return findings


def audit_entrypoints(specs, *, registered=None, compile_check: bool = False,
                      **thresholds) -> list:
    """Audit a spec list; ``registered`` (optional) is the name->metadata
    mapping the forensics/cost registries expose — any registered entry
    point missing from the audited set becomes a P3 coverage finding, so
    a new program added to the engines cannot silently skip the audit.
    ``compile_check=True`` turns on the memory_analysis aliasing
    cross-check for every spec (costs one compile per flagged program)."""
    findings = []
    audited = set()
    for spec in specs:
        spec = EntrypointSpec.normalize(spec)
        if compile_check:
            spec.compile_check = True
        audited.add(spec.name)
        try:
            findings.extend(audit_program(spec, **thresholds))
        except Exception as e:  # a spec that cannot trace is itself a finding
            findings.append(Finding(
                check="audit-trace-error", severity="P2", target=spec.name,
                message=f"could not trace {spec.name} for audit: {e!r}",
            ))
    for name in sorted(registered or ()):
        if name not in audited:
            findings.append(Finding(
                check="unaudited-entrypoint", severity="P3", target=name,
                message=f"{name} is registered with the forensics/cost "
                        "registry but absent from the audited entry-point "
                        "set — extend audit_entrypoints() coverage",
            ))
    return findings


def registered_names(telemetry=None) -> dict:
    """Merged name->metadata view of the forensics recorder and the cost
    registry (the registry-exposure contract the auditor audits against)."""
    out: dict = {}
    from ..telemetry import forensics

    rec = forensics.recorder()
    if rec is not None:
        out.update(rec.registered_entrypoints())
    costs = getattr(telemetry, "costs", None)
    if costs is not None:
        for name in costs.executable_names():
            out.setdefault(name, {})
    return out


def audit_engine(engine, *, cross_check_registry: bool = True,
                 compile_check: bool = False, **thresholds) -> list:
    """Audit a :class:`~..serving.engine.ServingEngine`'s full program
    set (what ``warmup()`` compiles), cross-checked against whatever the
    forensics/cost registries saw for this process."""
    registered = None
    if cross_check_registry:
        try:
            registered = registered_names(getattr(engine, "telemetry", None))
        except Exception:
            registered = None
    return audit_entrypoints(
        engine.audit_entrypoints(), registered=registered,
        compile_check=compile_check, **thresholds,
    )


def self_audit(*, include_train: bool = True, warmup: bool = False,
               compile_check: bool = False, **thresholds) -> list:
    """Audit the repo's own registered entry points: a paged
    tiny serving engine (the full warmup program set) and the fused
    train step, built on whatever backend is available. This is what
    ``accelerate-tpu audit`` and the tier-1 gate run; it needs jax but
    compiles nothing unless ``warmup=True``."""
    import jax

    from ..models import DecoderConfig, DecoderLM
    from ..parallel.sharding import unbox_params
    from ..serving import ServingEngine

    cfg = DecoderConfig.tiny(max_seq_len=64)
    model = DecoderLM(cfg)
    variables = model.init_variables(jax.random.PRNGKey(0), batch_size=1, seq_len=16)
    params, _ = unbox_params(variables["params"])
    engine = ServingEngine(
        model, params, num_slots=2, max_cache_len=64, prefill_chunks=(4, 8),
        page_size=8,
    )
    if warmup:
        engine.warmup()
    # ONE audit over the union of specs, with NO ambient-registry
    # cross-check: self_audit runs inside bench/CI processes where a live
    # telemetry session may have registered a *different* engine's
    # programs, and coverage findings against somebody else's registry
    # would make the published counts depend on session state. The
    # registry cross-check is audit_engine's job on a live engine.
    specs = list(engine.audit_entrypoints())
    errors = []
    if include_train:
        try:
            specs += _train_step_specs(cfg)
        except Exception as e:
            errors.append(Finding(
                check="audit-trace-error", severity="P2", target="train_step",
                message=f"could not build/trace the train step for audit: {e!r}",
            ))
    return audit_entrypoints(
        specs, compile_check=compile_check, **thresholds
    ) + errors


def _train_step_specs(cfg) -> list:
    import optax

    import jax
    import numpy as np

    from .. import Accelerator, Model
    from ..models import DecoderLM
    from ..state import AcceleratorState

    AcceleratorState._reset_state(reset_partial_state=False)
    accelerator = Accelerator()
    # the batch must divide the mesh's data-sharding degree or prepare()
    # refuses — on the 8-device CPU sim that degree is 8, not 1
    batch = 2
    mesh = accelerator.mesh
    if mesh is not None:
        degree = 1
        for ax in ("replica", "data", "fsdp"):
            degree *= mesh.shape.get(ax, 1)
        batch = max(batch, degree)
    model_def = DecoderLM(cfg, mesh=mesh)
    variables = model_def.init_variables(
        jax.random.PRNGKey(0), batch_size=batch, seq_len=16
    )
    accelerator.prepare(Model(model_def, variables), optax.adamw(3e-4))
    step = accelerator.build_train_step()
    ids = np.random.RandomState(0).randint(0, cfg.vocab_size, (batch, 16))
    batch = accelerator.prepare_for_eval({"input_ids": ids, "labels": ids})
    return accelerator.audit_entrypoints(step, batch)

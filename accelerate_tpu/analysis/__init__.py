"""Static analysis: program auditor + host-code linter.

Two analyzers behind one findings model and one CLI (``accelerate-tpu
audit``):

- :mod:`~.program_audit` walks the jaxpr/lowering of every registered
  jitted entry point (serving prefill/decode, the fused train
  step) for baked constants, donation misses, f32 drift, host callbacks
  and weak-shape dependencies — lazy-jax, tracing only.
- :mod:`~.host_lint` AST-lints the telemetry/serving host modules for
  lock-order inversions, user callbacks invoked under a lock, and
  env-var default traps — stdlib only, fully jax-free.
- :mod:`~.hygiene` declares THE jax-free module set (the single source
  of truth ``tests/test_imports.py`` derives its probes from) and
  statically checks import reachability against it.

Findings carry severities + stable fingerprints; ``audit-baseline.json``
suppresses the deliberate ones with a justification. See docs/audit.md.
"""

_LAZY = {
    "Finding": ("findings", "Finding"),
    "Baseline": ("findings", "Baseline"),
    "fingerprint": ("findings", "fingerprint"),
    "sort_findings": ("findings", "sort_findings"),
    "summarize": ("findings", "summarize"),
    "render_findings": ("findings", "render_findings"),
    "lint_paths": ("host_lint", "lint_paths"),
    "lint_source": ("host_lint", "lint_source"),
    "hygiene_findings": ("hygiene", "hygiene_findings"),
    "JAX_FREE_MODULES": ("hygiene", "JAX_FREE_MODULES"),
    "PALLAS_FREE_MODULES": ("hygiene", "PALLAS_FREE_MODULES"),
    "EntrypointSpec": ("program_audit", "EntrypointSpec"),
    "audit_program": ("program_audit", "audit_program"),
    "audit_entrypoints": ("program_audit", "audit_entrypoints"),
    "audit_engine": ("program_audit", "audit_engine"),
    "self_audit": ("program_audit", "self_audit"),
}

__all__ = sorted(_LAZY)


def __getattr__(name):
    try:
        module, attr = _LAZY[name]
    except KeyError:
        raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
    import importlib

    return getattr(importlib.import_module(f".{module}", __name__), attr)


def __dir__():
    return __all__

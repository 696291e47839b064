"""Continuous-batching serving: a paged KV cache with a copy-on-write
prefix cache, packed prefill admission and donated in-place batched
decode (docs/serving.md).

PEP 562 lazy re-exports: ``serving.pages`` is host-side bookkeeping
(free lists, refcounts, prefix hashing) that a
router/scheduler tier imports on machines with no accelerator stack, so
importing it must not drag the jax-heavy engine in (tests/test_imports).
"""

_EXPORTS = {
    "arena_nbytes": "pages",
    "Request": "engine",
    "ServingEngine": "engine",
    "generate_batched": "engine",
    "PageAllocator": "pages",
    "PrefixCache": "pages",
    "kv_cache_bits": "pages",
    "kv_token_bytes": "pages",
    "kv_quant_drift": "drift",
    # the policy tier (scheduler.py) and the fault harness (faults.py)
    # are jax-free like pages — a router tier imports them directly
    "MultiTenantScheduler": "scheduler",
    "PrefillBudgetController": "scheduler",
    "SchedulerConfig": "scheduler",
    "TenantConfig": "scheduler",
    "FaultInjector": "faults",
    "StreamDropped": "faults",
    # the multi-replica data plane: the router tier (jax-free) and the
    # per-replica HTTP wrapper (jax-free at import; wraps a live engine)
    "Router": "router",
    "RouterConfig": "router",
    "RouterServer": "router",
    "backoff_schedule": "router",
    "ReplicaServer": "replica_server",
}

__all__ = list(_EXPORTS)


def __getattr__(name):
    if name in _EXPORTS:
        import importlib

        mod = importlib.import_module(f".{_EXPORTS[name]}", __name__)
        return getattr(mod, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")

"""Version comparison helpers (parity: reference utils/versions.py)."""

from __future__ import annotations

import importlib.metadata
import operator

_OPS = {
    "<": operator.lt,
    "<=": operator.le,
    "==": operator.eq,
    "!=": operator.ne,
    ">=": operator.ge,
    ">": operator.gt,
}


def _parse(version: str) -> tuple:
    parts = []
    for piece in version.split("."):
        digits = "".join(ch for ch in piece if ch.isdigit())
        parts.append(int(digits) if digits else 0)
    return tuple(parts)


def compare_versions(library_or_version, operation: str, requirement_version: str) -> bool:
    """compare_versions("jax", ">=", "0.9.0") or compare_versions("0.9.0", "<", "1.0")."""
    if operation not in _OPS:
        raise ValueError(f"operation must be one of {sorted(_OPS)}, got {operation!r}")
    if isinstance(library_or_version, str) and not library_or_version[0].isdigit():
        library_or_version = importlib.metadata.version(library_or_version)
    return _OPS[operation](_parse(str(library_or_version)), _parse(requirement_version))


def is_jax_version(operation: str, version: str) -> bool:
    import jax

    return compare_versions(jax.__version__, operation, version)

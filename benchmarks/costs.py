"""Operations and bytes of the work that no architecture owns, from shapes
alone (no program code). What depends on the architecture (parameters,
operations a token, cache bytes a token and a decode step) is its module's,
``arch/<model_type>.py``.
"""

from __future__ import annotations


def page_rounded(write_pos: int, page_size: int) -> int:
    """Tokens the paged decode kernel walks in one full-attention layer for a
    sequence whose next write lands at ``write_pos``: whole pages up to and
    including that one. The engine counts the same where it grows the slots
    (``walked_tokens`` of its ``serving/decode_grow`` span)."""
    return (write_pos // page_size + 1) * page_size

#!/usr/bin/env python3
"""The standing proof that the trainer and the paged server run on the chip.

    python chip_smoke.py              # one TPU v5e chip: kernels, train, serve
    python chip_smoke.py --chips 4    # four chips: sharded training only

One process, one pass through the entry points a user calls, at the full
widths of ``DecoderConfig.llama_7b`` (embed 4096, 32 heads x 128, mlp 11008,
vocab 32000, untied); only depth is cut. Weights and data come from
``--seed``; nothing is read from the network. Phases on one chip:

- **kernels** — the pallas paged-decode and ragged-prefill kernels against
  their in-repo references (the gathered masked-dense read and
  ``_ragged_prefill_reference``), bf16 / int8 / int4 KV, MHA and GQA.
- **train** — ``Accelerator(mixed_precision="bf16")`` -> ``prepare`` ->
  ``build_train_step()``, a few steps on one fixed batch: the loss falls,
  the compiled step holds the flash kernel, nothing compiles after step 1.
- **serve** — ``ServingEngine(page_size=...)`` -> ``warmup()`` -> requests of
  mixed prompt length through ``submit``/``run``: every request finishes,
  nothing compiles after warm-up, the compiled decode and prefill programs
  hold their kernels, and the greedy tokens are compared with an engine
  built on the dense reference paths.

``--chips 4`` runs only the sharded trainer on a
``ShardingConfig(fsdp=2, tensor_parallel=2)`` mesh and the one-device plain
jax/optax run it is compared with (same weights, same batch).

Any failed check raises, so the exit code is non-zero and no result line is
printed. Without a TPU the script exits at once: ``--cpu-rehearsal`` is the
only way onto the CPU (tiny widths, kernels through the pallas interpreter),
and every line it prints says so — it checks control flow, never speed. The
last line on the chip is exactly
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}``.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import gc
import json
import logging
import math
import os
import re
import sys
import time

_PREFIX = ""


def say(msg: str = "") -> None:
    print(_PREFIX + msg, flush=True)


@dataclasses.dataclass(frozen=True)
class Sizes:
    """What one run holds. ``real`` is the chip; ``tiny`` the CPU rehearsal."""

    widths: dict           # overrides of DecoderConfig.llama_7b (none on the chip)
    kernel_mode: object    # decode/prefill kernel knob: None = compiled on the chip
    train_attention: str   # "auto" takes the flash kernel on the chip only
    seq_len: int
    train_layers: int
    train_batch: int
    train_steps: int
    serve_layers: int
    page_size: int
    num_slots: int
    max_cache_len: int
    prompt_lens: tuple     # plus two more that share a prefix of prompt_lens[-1] // 3
    new_tokens: int
    kv_heads_gqa: int
    kv_bits: tuple         # KV storage the kernel checks cover (0 = bf16)
    decode_widths: tuple   # query widths: 1 = decode step, 5 = several rows a slot (ROADMAP R12)


# Depths come from compiled.memory_analysis() of the AOT rehearsal for
# v5e:2x2 (compile only, PR 21): the train step at 2 layers x batch 4 x 2048
# holds 7.45 GiB of fp32 params + Adam state and 5.76 GiB of temporaries
# (13.2 GiB of the chip's 16; 3 layers do not fit); the serve programs at 8
# layers hold 3.5 GiB of bf16 weights, a 2 GiB KV arena and <= 3 GiB of
# temporaries (8.5 GiB).
REAL = Sizes(
    widths={}, kernel_mode=None, train_attention="auto", seq_len=2048,
    train_layers=2, train_batch=4, train_steps=6,
    serve_layers=8, page_size=16, num_slots=8, max_cache_len=2048,
    prompt_lens=(24, 57, 180, 640, 1500), new_tokens=64, kv_heads_gqa=8,
    kv_bits=(0, 8, 4), decode_widths=(1, 5),
)
TINY = Sizes(
    widths=dict(vocab_size=512, embed_dim=256, num_heads=2, mlp_dim=512),
    kernel_mode="interpret", train_attention="flash", seq_len=128,
    train_layers=2, train_batch=4, train_steps=5,
    serve_layers=2, page_size=8, num_slots=4, max_cache_len=256,  # >= the 256 prefill bucket
    prompt_lens=(5, 11, 30, 150), new_tokens=6, kv_heads_gqa=1,
    # the interpreter is slow: int4 only (the serve phase reads a bf16 arena)
    kv_bits=(4,), decode_widths=(5,),
)

# bf16 has an 8-bit mantissa: outputs of O(1) agree to a few 2^-8 steps
BF16_ATOL = BF16_RTOL = 2e-2
BF16_STEP = 2.0 ** -7  # spacing of bf16 values in [1, 2)


def compile_counts() -> dict:
    from accelerate_tpu.utils.compile_cache import compile_event_counters

    return compile_event_counters()


@contextlib.contextmanager
def steady_window(what: str):
    """A window in which nothing may compile. The count is the library's own
    (``compile_event_counters``: one event for each backend compile, a
    persistent-cache hit included); jax's compile log is captured alongside,
    so a failure names the program."""
    import jax

    names = []

    class Collect(logging.Handler):
        def emit(self, record):
            msg = record.getMessage()
            if msg.startswith(("Compiling ", "Finished tracing")):
                names.append(msg.split(" with global shapes")[0].split(" for pjit")[0])

    logger, handler = logging.getLogger("jax"), Collect()
    logger.addHandler(handler)
    propagate, logger.propagate = logger.propagate, False
    before = compile_counts()["count"]
    try:
        with jax.log_compiles():
            yield
    finally:
        logger.removeHandler(handler)
        logger.propagate = propagate
    events = compile_counts()["count"] - before
    if events:
        raise AssertionError(f"{events} compile events {what}: {names}")


def assert_kernel_in(compiled_text: str, what: str, on_chip: bool) -> None:
    """The compiled program must hold the Mosaic kernel, not a reference path."""
    if on_chip:
        if "tpu_custom_call" not in compiled_text:
            raise AssertionError(f"{what}: no tpu_custom_call in the compiled program")
        say(f"  {what}: tpu_custom_call present")
    else:
        say(f"  {what}: interpreter run, no Mosaic kernel to look for")


def peak_memory(dev) -> str:
    stats = dev.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    return f"{peak / 2**30:.2f} GiB" if peak is not None else "not reported by this backend"


def llama_cfg(S: Sizes, num_layers: int, **kw):
    from accelerate_tpu.models import DecoderConfig

    return DecoderConfig.llama_7b(
        num_layers=num_layers, max_seq_len=S.max_cache_len, scan_layers=True,
        **S.widths, **kw,
    )


def train_cfg(S: Sizes):
    # remat + scan as the bench flagship uses
    return llama_cfg(S, S.train_layers, remat=True, remat_policy="save_dots",
                     attention_impl=S.train_attention)


# ---------------------------------------------------------------------------
# kernels against their references
# ---------------------------------------------------------------------------


def _quantized_arena(rng, shape, bits):
    """(payload, scale) the way the cache holds them: quantize_kv of N(0,1)."""
    import jax
    import jax.numpy as jnp

    from accelerate_tpu.utils.quantization import quantize_kv

    x = jnp.asarray(rng.standard_normal(shape), jnp.bfloat16)
    return jax.jit(quantize_kv, static_argnums=1)(x, bits) if bits else (x, None)


def check_paged_decode(S: Sizes, rng, *, kvh, bits) -> dict:
    """{query width: (max |kernel - reference|, whether the gate let the
    kernel run)} over one arena. On the chip the paged kernel takes
    unquantized 128-wide pages only; the rest compares the dense path with
    itself, and the line says so."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from accelerate_tpu.ops.attention import (
        _decode_kernel_gate, paged_decode_attention, resolve_decode_kernel)

    cfg = llama_cfg(S, 1)
    h, d, ps = cfg.num_heads, cfg.head_dim, S.page_size
    slots, per = S.num_slots, S.max_cache_len // S.page_size
    pages = 1 + slots * per
    k_pages, k_scale = _quantized_arena(rng, (pages, kvh, ps, d), bits)
    v_pages, v_scale = _quantized_arena(rng, (pages, kvh, ps, d), bits)
    kw = dict(page_table=jnp.asarray(
        1 + rng.permutation(slots * per).reshape(slots, per).astype(np.int32)))
    if bits:
        kw.update(k_scale=k_scale, v_scale=v_scale, kv_quant_bits=bits)
    errs = {}
    for sq in S.decode_widths:
        last = rng.randint(sq, S.max_cache_len, size=(slots,))
        last[0], last[-1] = sq, S.max_cache_len - 1  # shortest and longest cache
        pos = jnp.asarray((last[:, None] - np.arange(sq)[::-1][None, :]).astype(np.int32))
        q = jnp.asarray(rng.standard_normal((slots, h, sq, d)), jnp.bfloat16)
        run = lambda impl: jax.jit(
            lambda q, k, v: paged_decode_attention(q, k, v, impl=impl, q_positions=pos, **kw)
        )(q, k_pages, v_pages)
        out = np.asarray(run(S.kernel_mode), np.float32)
        ref = np.asarray(run("dense"), np.float32)
        np.testing.assert_allclose(out, ref, atol=BF16_ATOL, rtol=BF16_RTOL)
        kernel, _ = _decode_kernel_gate(
            resolve_decode_kernel(S.kernel_mode), sq, d, ps, bits, paged=True)
        errs[sq] = float(np.max(np.abs(out - ref))), kernel
    return errs


def check_ragged_prefill(S: Sizes, rng, *, kvh, bits):
    """A packed dispatch of four admissions: a cold tail, two tails behind a
    cached prefix (one ending on a page boundary), a one-token tail."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from accelerate_tpu.ops.attention import _PREFILL_TOKEN_BLOCK as bt
    from accelerate_tpu.ops.attention import _prefill_kernel_gate, ragged_prefill_attention, resolve_prefill_kernel
    from accelerate_tpu.utils.quantization import unpack_int4_kv

    cfg = llama_cfg(S, 1)
    h, d, ps = cfg.num_heads, cfg.head_dim, S.page_size
    per = S.max_cache_len // ps
    unit = S.max_cache_len // 16
    packs = [(0, unit - 3), (4 * unit, unit // 2 + 1), (6 * unit - unit // 2, unit // 2), (9 * unit + 5, 1)]
    cap = sum(-(-t // bt) * bt for _, t in packs)
    row_slot = np.full((cap,), -1, np.int32)
    row_pos = np.full((cap,), -1, np.int32)
    slot_hist = np.zeros((len(packs),), np.int32)
    table = np.zeros((len(packs), per), np.int32)
    r = 0
    for s, (hist, tail) in enumerate(packs):
        rows = -(-tail // bt) * bt
        row_slot[r:r + rows] = s
        row_pos[r:r + tail] = np.arange(hist, hist + tail)
        r += rows
        slot_hist[s] = hist
        need = -(-(hist + tail) // ps)
        table[s, :need] = 1 + s * per + np.arange(need)
    pages = 1 + len(packs) * per
    k_pages, k_scale = _quantized_arena(rng, (pages, kvh, ps, d), bits)
    v_pages, v_scale = _quantized_arena(rng, (pages, kvh, ps, d), bits)
    q = jnp.asarray(rng.standard_normal((1, h, cap, d)), jnp.bfloat16)
    k_new = jnp.asarray(rng.standard_normal((1, kvh, cap, d)), jnp.bfloat16)
    v_new = jnp.asarray(rng.standard_normal((1, kvh, cap, d)), jnp.bfloat16)
    kw = dict(page_table=jnp.asarray(table), row_slot=jnp.asarray(row_slot),
              row_pos=jnp.asarray(row_pos), slot_hist=jnp.asarray(slot_hist),
              kv_quant_bits=bits)
    if bits:
        kw.update(k_scale=k_scale, v_scale=v_scale)
    run = lambda impl: jax.jit(
        lambda *a: ragged_prefill_attention(*a, impl=impl, **kw)
    )(q, k_new, v_new, k_pages, v_pages)
    got, ref = run(S.kernel_mode), run("dense")
    valid = (row_slot >= 0) & (row_pos >= 0)
    out = np.asarray(got[0], np.float32)[0][:, valid]
    out_ref = np.asarray(ref[0], np.float32)[0][:, valid]
    np.testing.assert_allclose(out, out_ref, atol=BF16_ATOL, rtol=BF16_RTOL)
    byte_mismatch = 0.0
    if bits:
        # quantize-on-write: same scales, and payloads that differ by at most
        # one step on a vanishing share of values (Mosaic and XLA may round
        # x * (1 / scale) differently on an exact tie)
        for pay, scl, pay_ref, scl_ref in ((got[1], got[2], ref[1], ref[2]),
                                           (got[3], got[4], ref[3], ref[4])):
            np.testing.assert_allclose(
                np.asarray(scl)[valid], np.asarray(scl_ref)[valid], rtol=1e-6)
            if bits == 4:
                pay, pay_ref = unpack_int4_kv(pay), unpack_int4_kv(pay_ref)
            diff = np.abs(np.asarray(pay, np.int32)[valid] - np.asarray(pay_ref, np.int32)[valid])
            if diff.max() > 1:
                raise AssertionError(f"quantize-on-write payload off by {diff.max()} steps")
            byte_mismatch = max(byte_mismatch, float((diff > 0).mean()))
        if byte_mismatch > 1e-3:
            raise AssertionError(f"quantize-on-write payload mismatch share {byte_mismatch:.2e}")
    kernel, _ = _prefill_kernel_gate(resolve_prefill_kernel(S.kernel_mode), d, ps, bt, bits)
    return float(np.max(np.abs(out - out_ref))), byte_mismatch, kernel


def kernels_phase(S: Sizes, seed: int, on_chip: bool) -> None:
    import numpy as np

    rng = np.random.RandomState(seed)
    heads = llama_cfg(S, 1).num_heads
    say(f"  tolerance: atol={BF16_ATOL} rtol={BF16_RTOL} (bf16 outputs), "
        f"kernel mode {S.kernel_mode or 'compiled (Mosaic)'}")
    for kvh in (heads, S.kv_heads_gqa):
        for bits in S.kv_bits:
            kv = {0: "bf16", 8: "int8", 4: "int4"}[bits]
            for sq, (err, kernel) in check_paged_decode(S, rng, kvh=kvh, bits=bits).items():
                say(f"  paged decode   {heads}q/{kvh}kv {kv} Sq={sq}: max|kernel-ref|={err:.4f}"
                    + ("" if kernel else " (gated to the dense path: no kernel ran)"))
            err, mism, kernel = check_ragged_prefill(S, rng, kvh=kvh, bits=bits)
            say(f"  ragged prefill {heads}q/{kvh}kv {kv}: max|kernel-ref|={err:.4f}"
                + (f", payload mismatch share={mism:.1e}" if bits else "")
                + ("" if kernel else " (gated to the dense path: no kernel ran)"))


def ssm_phase(S: Sizes, seed: int, on_chip: bool) -> None:
    """The ``ssm_scan`` kernel against ``selective_scan_reference`` at the
    published widths of the state-space cell (5120 channels x 16, 128 slots;
    tiny and interpreted in the rehearsal): a decode step, one row a slot
    with idle slots between, and a pack of four 64-row token blocks (a fresh
    request over two blocks, a resumed one whose block is partial, padding),
    on a stack of two layers of which the second is advanced. What no block
    advances has to come back bit for bit."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from accelerate_tpu.ops.ssm import resolve_ssm_kernel, ssm_scan

    width, n, slots, bt = (5120, 16, 128, 64) if on_chip else (256, 4, 8, 8)
    mode = resolve_ssm_kernel(S.kernel_mode)
    live = (np.arange(slots) % 5 != 3).astype(np.int32)
    shapes = {
        "decode step": (np.arange(slots), live, np.zeros(slots, np.int32), 1),
        "packed prefill": (np.array([2, 2, 0, -1]), np.array([bt, bt - 3, bt // 2 + 1, 0]), np.array([1, 0, 0, 0]), bt),
    }
    for name, (slot, rows, fresh, block) in shapes.items():
        key = jax.random.split(jax.random.key(seed + block), 7)
        nb = len(slot)
        args = (jax.random.normal(key[0], (nb, block, width)).astype(jnp.bfloat16),
                jax.nn.softplus(jax.random.normal(key[1], (nb, block, width)) - 3.0),
                jax.random.normal(key[2], (nb, block, n)), jax.random.normal(key[3], (nb, block, n)),
                -jnp.exp(0.5 * jax.random.normal(key[4], (n, width))), jax.random.normal(key[5], (width,)),
                jax.random.normal(key[6], (2, slots, n, width)))
        kw = dict(block_slot=jnp.asarray(slot, jnp.int32), block_rows=jnp.asarray(rows, jnp.int32),
                  block_fresh=jnp.asarray(fresh, jnp.int32), layer=1)
        run = {impl: jax.jit(lambda *a, impl=impl: ssm_scan(*a, impl=impl, **kw)) for impl in (mode, "reference")}
        (y, state), (y_ref, state_ref) = (jax.block_until_ready(run[impl](*args)) for impl in (mode, "reference"))
        err_y = max(float(jnp.max(jnp.abs(y[j, :r] - y_ref[j, :r]))) for j, r in enumerate(rows) if slot[j] >= 0 and r)
        err_s = float(jnp.max(jnp.abs(state - state_ref)))
        np.testing.assert_allclose(np.asarray(state), np.asarray(state_ref), atol=1e-4, rtol=1e-4)
        assert err_y <= 1e-3, err_y
        advanced = {int(s) for s, r, f in zip(slot, rows, fresh) if s >= 0 and (r or f)}
        kept = [i for i in range(slots) if i not in advanced]
        assert np.array_equal(np.asarray(state[0]), np.asarray(args[-1][0]))
        assert np.array_equal(np.asarray(state[1, kept]), np.asarray(args[-1][1, kept]))
        line = f"  ssm_scan {name} ({nb} blocks x {block} rows, {width} x {n}, {mode}): max|y-ref|={err_y:.2e} max|S-ref|={err_s:.2e}"
        if on_chip:
            t0 = time.perf_counter()
            for _ in range(20):
                out = run[mode](*args)
            jax.block_until_ready(out)
            line += f"; {1e6 * (time.perf_counter() - t0) / 20:.0f} us a call, host clock, dispatch included"
        say(line)


def ssd_phase(S: Sizes, seed: int, on_chip: bool) -> None:
    """The two kernels ISSUE 44 brought, compiled by Mosaic at the published
    shapes of the Nemotron-3-Super cell against their ``jax.numpy`` reads (tiny
    and interpreted in the rehearsal): ``ssd_scan`` (128 heads x 64 x 128 of
    state a slot, 8 groups, 96 slots: a decode step with dead slots between,
    and a pack of four 64-row token blocks, on a stack of two layers of which
    the second is advanced; what no block advances comes back bit for bit) and
    ``moe_experts_relu2`` (128 two-matrix experts of 1,024 -> 2,688 -> 1,024 out
    of a stack of two layers, at a decode step's 1,056 rows and a pack's 2,816,
    against a loop over the experts by hand)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from accelerate_tpu.models.moe import grouped_mlp
    from accelerate_tpu.ops.ssm import resolve_ssm_kernel, ssd_scan, ssd_state_shape

    def timed(fn, args, line):
        if on_chip:
            t0 = time.perf_counter()
            for _ in range(20):
                out = fn(*args)
            jax.block_until_ready(out)
            line += f"; {1e6 * (time.perf_counter() - t0) / 20:.0f} us a call, host clock, dispatch included"
        say(line)

    heads, p, groups, n, slots, bt = (128, 64, 8, 128, 96, 64) if on_chip else (8, 8, 2, 16, 8, 8)
    width, mode = heads * p, resolve_ssm_kernel(S.kernel_mode)
    live = (np.arange(slots) % 5 != 3).astype(np.int32)
    shapes = {
        "decode step": (np.arange(slots), live, np.zeros(slots, np.int32), 1),
        "packed prefill": (np.array([2, 2, 0, -1]), np.array([bt, bt - 3, bt // 2 + 1, 0]), np.array([1, 0, 0, 0]), bt),
    }
    a_head = lambda v: jnp.repeat(v, p, axis=-1)
    for name, (slot, rows, fresh, block) in shapes.items():
        key = jax.random.split(jax.random.key(seed + block), 7)
        nb = len(slot)
        args = (jax.random.normal(key[0], (nb, block, width)),
                a_head(jax.nn.softplus(jax.random.normal(key[1], (nb, block, heads)) - 3.0)),
                jax.random.normal(key[2], (nb, block, groups, n)), jax.random.normal(key[3], (nb, block, groups, n)),
                a_head(-jnp.exp(0.5 * jax.random.normal(key[4], (heads,)))), a_head(jax.random.normal(key[5], (heads,))),
                jax.random.normal(key[6], (2, slots, *ssd_state_shape(width, groups, n))))
        kw = dict(block_slot=jnp.asarray(slot, jnp.int32), block_rows=jnp.asarray(rows, jnp.int32),
                  block_fresh=jnp.asarray(fresh, jnp.int32), layer=1)
        run = {impl: jax.jit(lambda *a, impl=impl: ssd_scan(*a, impl=impl, **kw)) for impl in (mode, "reference")}
        (y, state), (y_ref, state_ref) = (jax.block_until_ready(run[impl](*args)) for impl in (mode, "reference"))
        err_y = max(float(jnp.max(jnp.abs(y[j, :r] - y_ref[j, :r]))) for j, r in enumerate(rows) if slot[j] >= 0 and r)
        err_s = float(jnp.max(jnp.abs(state - state_ref)))
        # (compared on the device: a stack of states is 0.8 GB at the published shapes)
        assert err_s <= 1e-4 * (1.0 + float(jnp.max(jnp.abs(state_ref)))) and err_y <= 2e-3, (err_s, err_y)
        advanced = {int(s) for s, r, f in zip(slot, rows, fresh) if s >= 0 and (r or f)}
        kept = jnp.asarray([i for i in range(slots) if i not in advanced])
        assert bool(jnp.array_equal(state[0], args[-1][0]))
        assert bool(jnp.array_equal(state[1, kept], args[-1][1, kept]))
        timed(run[mode], args, f"  ssd_scan {name} ({nb} blocks x {block} rows, {heads} x {p} x {n}, {groups} groups, "
                               f"{mode}): max|y-ref|={err_y:.2e} max|S-ref|={err_s:.2e}")

    held, d, m = (128, 1024, 2688) if on_chip else (8, 32, 128)
    impl = "pallas" if on_chip else "interpret"
    key = jax.random.split(jax.random.key(seed + 7), 4)
    wu = (jax.random.normal(key[0], (2, held, d, m)) * d ** -0.5).astype(jnp.bfloat16)
    wd = (jax.random.normal(key[1], (2, held, m, d)) * m ** -0.5).astype(jnp.bfloat16)
    for name, rows in (("decode step", 1056 if on_chip else 48), ("packed prefill", 2816 if on_chip else 96)):
        xs = jax.random.normal(key[2], (rows, d))  # float32 rows, which the kernel multiplies in two terms
        # the expected load: half the rows filled, two experts without a pair, sizes uneven
        load = np.random.default_rng(seed).multinomial(rows // 2, np.ones(held - 2) / (held - 2))
        sizes = jnp.asarray(np.concatenate([load[:3], [0], load[3:-1], [0], load[-1:]]), jnp.int32)
        # (the stacks are arguments: a jitted function that closes over 1.4 GB bakes it into its program)
        kernel = jax.jit(lambda x, s, wu, wd: grouped_mlp(x, None, wu, wd, s, impl, layer=jnp.int32(1)))
        got = np.asarray(jax.block_until_ready(kernel(xs, sizes, wu, wd)))
        # by hand, an expert at a time over its own rows, in float32
        want, lo, x32 = np.zeros((rows, d), np.float32), 0, np.asarray(xs, np.float32)
        for e, n in enumerate(np.asarray(sizes)):
            if n:
                hidden = np.square(np.maximum(x32[lo:lo + n] @ np.asarray(wu[1, e], np.float32), 0.0))
                want[lo:lo + n] = hidden @ np.asarray(wd[1, e], np.float32)
            lo += n
        err, scale = float(np.abs(got - want).max()), float(np.abs(want).max())
        assert err <= 2e-4 * max(scale, 1.0), (err, scale)
        assert not got[rows // 2:].any()
        timed(kernel, (xs, sizes, wu, wd), f"  moe_experts_relu2 {name} ({rows} rows, {held} experts of {d} -> {m} -> {d}, "
                                   f"{int((np.asarray(sizes) > 0).sum())} with a pair, {impl}): max|y-ref|={err:.2e} of {scale:.2f}")


def gdn_phase(S: Sizes, seed: int, on_chip: bool) -> None:
    """The delta rule ISSUE 48 brought, against the ``jax.numpy`` row rule at
    the published shapes of the Qwen3-Next cell (32 value heads over 16 key
    heads of 128 x 128, 2 MB of state a slot, 128 slots; tiny and interpreted
    in the rehearsal), on a stack of two layers of which the second is
    advanced; what no block advances comes back bit for bit. The ``gdn_scan``
    kernel compiled by Mosaic in both its forms: a decode step with dead slots
    between (a block of one row: the row walk), and a pack of four 64-row token
    blocks (the chunked form on the matrix unit, ISSUE 49) at 64, 32, 16 and 8
    live rows a block. Beside each pack, where the parent commit is unpacked
    under ``.parent_tree`` (as a builder compares two commits in one call), the
    parent's kernel on the same rows, and the chunked form through XLA
    (``ops/ssm.gdn_chunked``, which no program calls). Each is timed with the
    stack donated and handed on from call to call, as both served programs
    hold it. And the gated experts' two kernels, each at rows on both sides of
    the size at which the program changes from one to the other, against a
    loop over the experts by hand."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from accelerate_tpu.ops.ssm import gdn_chunked, gdn_scan, resolve_ssm_kernel

    def timed(fn, args, line, carried=None):
        # ``carried``: the argument the call hands on (the stack of states, donated); else the same arguments each
        # call. The first call, which compiles, is not timed
        if on_chip:
            args = list(args)
            if carried is not None:
                args[carried] = jnp.copy(args[carried])
            for i in range(21):
                out = fn(*args)
                if carried is not None:
                    args[carried] = out[1]
                if i == 0:
                    jax.block_until_ready(out)
                    t0 = time.perf_counter()
            jax.block_until_ready(out)
            line += f"; {1e6 * (time.perf_counter() - t0) / 20:.0f} us a call, host clock, dispatch included"
        say(line)

    hk, hv, dk, dv, slots, bt = (16, 32, 128, 128, 128, 64) if on_chip else (2, 4, 8, 8, 8, 8)
    mode = resolve_ssm_kernel(S.kernel_mode)
    served = lambda *a, **kw: gdn_scan(*a, impl=mode, **kw)
    forms = {f"gdn_scan, {mode}": served}
    parent = os.path.join(os.path.dirname(os.path.abspath(__file__)), ".parent_tree", "accelerate_tpu", "ops", "ssm.py")
    if on_chip and os.path.exists(parent):
        with open(parent) as f:
            source = f.read().replace("from .attention import", "from accelerate_tpu.ops.attention import")
        parent_ssm = type(os)("parent_ssm")
        exec(compile(source, parent, "exec"), parent_ssm.__dict__)
        forms["the parent's gdn_scan"] = lambda *a, **kw: parent_ssm.gdn_scan(*a, impl=mode, **kw)
    forms[f"gdn_chunked through XLA, {bt} rows a chunk"] = lambda *a, **kw: gdn_chunked(*a, chunk=bt, **kw)
    live = (np.arange(slots) % 5 != 3).astype(np.int32)
    shapes = {"decode step": (np.arange(slots), live, np.zeros(slots, np.int32), 1)}
    for n in sorted({bt, bt // 2, bt // 4, max(bt // 8, 1)}, reverse=True):
        shapes[f"packed prefill, {n} live rows a block"] = (
            np.array([2, 2, 0, -1]), np.array([n, n - n // 8, n // 2 + 1, 0]), np.array([1, 0, 0, 0]), bt)
    unit = lambda x: x * jax.lax.rsqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True) + 1e-6)
    for name, (slot, rows, fresh, block) in shapes.items():
        key = jax.random.split(jax.random.key(seed + block), 6)
        nb = len(slot)
        args = (unit(jax.random.normal(key[0], (nb, block, hk, dk))) * dk ** -0.5,
                unit(jax.random.normal(key[1], (nb, block, hk, dk))), jax.random.normal(key[2], (nb, block, hv, dv)),
                -jax.nn.softplus(jax.random.normal(key[3], (nb, block, hv)) - 2.0),
                jax.nn.sigmoid(jax.random.normal(key[4], (nb, block, hv))),
                jax.random.normal(key[5], (2, slots, hv, dk, dv)))
        kw = dict(block_slot=jnp.asarray(slot, jnp.int32), block_rows=jnp.asarray(rows, jnp.int32),
                  block_fresh=jnp.asarray(fresh, jnp.int32), layer=1)
        o_ref, state_ref = jax.block_until_ready(jax.jit(lambda *a: gdn_scan(*a, impl="reference", **kw))(*args))
        say(f"  gdn_scan {name} ({nb} blocks x {block} rows, {hv} heads over {hk} of {dk} x {dv}, the stack donated):")
        for form, rule in forms.items():
            if block == 1 and rule is not served:  # (the comparisons are a pack's)
                continue
            fn = lambda *a: rule(*a, **kw)
            o, state = jax.block_until_ready(jax.jit(fn)(*args))
            err_o = max(float(jnp.max(jnp.abs(o[j, :r] - o_ref[j, :r]))) for j, r in enumerate(rows) if slot[j] >= 0 and r)
            err_s = float(jnp.max(jnp.abs(state - state_ref)))
            # (compared on the device: a stack of states is 0.5 GB at the published shapes)
            assert err_s <= 1e-4 * (1.0 + float(jnp.max(jnp.abs(state_ref)))) and err_o <= 2e-3, (form, err_s, err_o)
            advanced = {int(s) for s, r, f in zip(slot, rows, fresh) if s >= 0 and (r or f)}
            kept = jnp.asarray([i for i in range(slots) if i not in advanced])
            assert bool(jnp.array_equal(state[0], args[-1][0]))
            assert bool(jnp.array_equal(state[1, kept], args[-1][1, kept]))
            timed(jax.jit(fn, donate_argnums=5), args,
                  f"    {form}: max|o - row rule|={err_o:.2e} max|S - row rule|={err_s:.2e}", carried=5)

    # the gated experts' two kernels on either side of models/moe._ALL_ROWS_MAX (every expert over every row up to
    # 256 rows, each expert's own row tiles past it), both timed at every size so that the constant stands beside a
    # measurement: the cell's 64 narrow experts of 2,048 -> 512 -> 2,048 from a quarter of a decode step's rows to a
    # pack's 640, and the two standing expert cells' shapes at the ends of the rows their calls have; out of a stack
    # of two layers, against a loop over the experts by hand
    from accelerate_tpu.models import moe

    impl = "pallas" if on_chip else "interpret"
    cases = ((("qwen3-next", 64, 2048, 512, (64, 128, 256, 320, 640)), ("mimo", 16, 4096, 2048, (64, 256)),
              ("gigachat3", 8, 7168, 2048, (16, 128))) if on_chip else (("tiny", 8, 32, 128, (64, 320)),))
    for model, held, d, m, sizes_of_rows in cases:
        key = jax.random.split(jax.random.key(seed + 7), 4)
        wg, wu = ((jax.random.normal(k, (2, held, d, m)) * d ** -0.5).astype(jnp.bfloat16) for k in key[:2])
        wd = (jax.random.normal(key[2], (2, held, m, d)) * m ** -0.5).astype(jnp.bfloat16)
        for rows in sizes_of_rows:
            xs = jax.random.normal(key[3], (rows, d)).astype(jnp.bfloat16)
            # half the rows filled, two experts without a pair, sizes uneven
            load = np.random.default_rng(seed).multinomial(rows // 2, np.ones(held - 2) / (held - 2))
            sizes = jnp.asarray(np.concatenate([load[:3], [0], load[3:-1], [0], load[-1:]]), jnp.int32)
            want, lo, x32 = np.zeros((rows, d), np.float32), 0, np.asarray(xs, np.float32)
            for e, n in enumerate(np.asarray(sizes)):
                if n:
                    gate, up = (x32[lo:lo + n] @ np.asarray(w[1, e], np.float32) for w in (wg, wu))
                    hidden = np.asarray(jnp.asarray(gate / (1.0 + np.exp(-gate)) * up).astype(jnp.bfloat16), np.float32)
                    want[lo:lo + n] = hidden @ np.asarray(wd[1, e], np.float32)
                lo += n
            scale = float(np.abs(want).max())
            served = "every expert over every row" if rows <= moe._ALL_ROWS_MAX else "each expert's own row tiles"
            forms = {"every expert over every row": lambda x, s, wg, wu, wd: moe._experts_all_rows_call(
                         x, wg, wu, wd, s, jnp.int32(1), impl == "interpret"),
                     "each expert's own row tiles": lambda x, s, wg, wu, wd: moe._experts2_kernel_call(
                         x, wu, wd, s, jnp.int32(1), impl == "interpret", wg=wg)}
            forms[served + ", as served"] = lambda x, s, wg, wu, wd: moe.grouped_mlp(
                x, wg, wu, wd, s, impl, layer=jnp.int32(1))
            del forms[served]
            for form, fn in forms.items():
                line = (f"  moe_experts {model} ({rows} rows, {held} experts of {d} -> {m} -> {d}, "
                        f"{int((np.asarray(sizes) > 0).sum())} with a pair, {impl}, {form})")
                kernel = jax.jit(fn)
                try:
                    got = np.asarray(jax.block_until_ready(kernel(xs, sizes, wg, wu, wd)))
                except Exception as e:  # a form the chip's compiler refuses at a shape it does not serve
                    assert "as served" not in form, e
                    say(f"{line}: not compiled, {str(e).splitlines()[0][:160]}")
                    continue
                err = float(np.abs(got - want).max())
                assert err <= 4e-3 * max(scale, 1.0), (form, err, scale)
                assert not got[rows // 2:].any()
                timed(kernel, (xs, sizes, wg, wu, wd), f"{line}: max|y-ref|={err:.2e} of {scale:.2f}")


def eva_phase(S: Sizes, seed: int, on_chip: bool) -> None:
    """The ``eva_pool`` kernel against ``eva_pool_reference`` at the published
    widths of the closing-window cell (32 kv heads of 128, pages of 16, a
    stack of two layers of which the second is pooled; tiny and interpreted
    in the rehearsal): a decode step in which some slots fill a page (two
    fill rows of one destination page, the others name the parking page) and
    one in which all 16 do. What no step names has to come back bit for bit."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from accelerate_tpu.ops.eva import eva_pool_pages, eva_pool_reference

    kvh, d, ps, pages = (32, 128, 16, 512) if on_chip else (4, 16, 4, 40)
    mode = S.kernel_mode  # None on the chip: the compiled kernel
    key = jax.random.split(jax.random.key(seed + 38), 4)
    kp, vp = (jax.random.normal(k, (2, pages, kvh, ps, d)).astype(jnp.bfloat16) for k in key[:2])
    mu, phi = (jax.random.normal(k, (kvh, d)) for k in key[2:])
    rng = np.random.RandomState(seed)
    for name, n in (("some slots fill a page", 16), ("every slot fills a page", 16)):
        src = rng.permutation(np.arange(1, pages // 2))[:n]
        if name.startswith("some"):
            src[rng.rand(n) < 0.5] = 0  # slots that fill no page
        dst = np.where(src > 0, pages // 2 + np.arange(n) // 2, 0)  # two steps a destination page
        off = np.where(src > 0, rng.randint(0, ps // 2, n) * 2 + np.arange(n) % 2, 0)
        args = tuple(jnp.asarray(x, jnp.int32) for x in (src, dst, off))
        run = jax.jit(lambda k, v: eva_pool_pages(
            k, v, mu, phi, *args, sm_scale=d ** -0.5, layer=1, interpret=mode == "interpret"))
        got_k, got_v = jax.block_until_ready(run(kp, vp))
        want_k, want_v = eva_pool_reference(kp[1], vp[1], mu, phi, *args, d ** -0.5)
        err = max(float(jnp.max(jnp.abs(g[1, 1:].astype(jnp.float32) - w[1:].astype(jnp.float32))))
                  for g, w in ((got_k, want_k), (got_v, want_v)))
        assert err <= BF16_ATOL, err
        assert np.array_equal(np.asarray(got_k[0]), np.asarray(kp[0]))  # the other layer
        named = set(int(p) for p in dst if p)
        kept = [p for p in range(1, pages) if p not in named]
        assert np.array_equal(np.asarray(got_k[1, kept]), np.asarray(kp[1, kept]))
        assert np.array_equal(np.asarray(got_v[1, kept]), np.asarray(vp[1, kept]))
        line = (f"  eva_pool {name} ({int((src > 0).sum())} of {n} steps pool, {kvh} kv heads x {d}, pages of {ps}, "
                f"{mode or 'compiled (Mosaic)'}): max|kernel-ref|={err:.4f}")
        if on_chip:
            t0 = time.perf_counter()
            for _ in range(20):
                out = run(kp, vp)
            jax.block_until_ready(out)
            line += f"; {1e6 * (time.perf_counter() - t0) / 20:.0f} us a call, host clock, dispatch and the stack's copy included"
        say(line)


def latent_phase(S: Sizes, seed: int, on_chip: bool) -> None:
    """Latent attention's two kernels against their dense reads at the
    published widths of the gigachat3 cell (64 query heads against one entry
    of 512 + 64 values stored in 640 lanes, pages of 16, a stack of two layers
    of which the second is read and written; tiny and interpreted in the
    rehearsal): a decode step over slots at four depths, one idle, and a pack
    of two slots' rows behind cached entries, both writing their new entries
    in place. On the chip also what the two forms of a pack's attention cost
    at depths 2k, 8k and 24k: the absorbed kernel over the entries as stored,
    against up-projecting the slot's cached entries into every head's keys
    and values (a gather and one product) and attending those (XLA's dense
    attention: no kernel takes 192-wide heads with a shared rotated key)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from accelerate_tpu.ops import attention as A

    h, r, p, n, dv, ps, pages = (64, 512, 64, 128, 192, 16, 4096) if on_chip else (4, 32, 8, 16, 16, 8, 64)
    lanes = A.paged_key_lanes(r + p)
    mode = S.kernel_mode  # None on the chip: the compiled kernel
    scale = (n + p) ** -0.5
    key = jax.random.split(jax.random.key(seed + 42), 6)
    live = jnp.arange(lanes) < r + p  # the lanes past the entry are zeros, as stored
    rand = lambda k, *shape: jnp.where(live, jax.random.normal(k, shape + (lanes,)), 0.0).astype(jnp.bfloat16)
    stack = rand(key[0], 2, pages, 1, ps)
    per_slot = pages // 4 // 2
    table = jnp.asarray(np.random.RandomState(seed).permutation(np.arange(1, pages))[:4 * per_slot].reshape(4, per_slot),
                        jnp.int32)
    err_of = lambda got, want: float(jnp.max(jnp.abs(got.astype(jnp.float32) - want.astype(jnp.float32))))

    # a decode step: slots at four depths, the first idle
    depths = (0, 5 * ps + 3, per_slot * ps // 2, per_slot * ps - 1)
    lengths = jnp.asarray(depths, jnp.int32)
    pos = jnp.maximum(lengths - 1, 0)[:, None]
    q, new = rand(key[1], 4, h, 1), rand(key[2], 4, 1, 1)
    step = jax.jit(lambda st: A.paged_latent_attention(
        q, st, page_table=table, q_positions=pos, latent=r, sm_scale=scale, kv_lengths=lengths, impl=mode,
        layer=jnp.int32(1), new=new))
    got, written = jax.block_until_ready(step(stack))
    page = table[jnp.arange(4)[:, None], pos // ps]
    keep = (lengths == 0)[:, None, None, None]
    scattered = stack[1].at[page, :, pos % ps].set(jnp.where(keep, stack[1][page, :, pos % ps], jnp.swapaxes(new, 1, 2)))
    want = A.paged_latent_attention(q, scattered, page_table=table, q_positions=pos, latent=r, sm_scale=scale,
                                    kv_lengths=lengths, impl="dense")
    err = err_of(got[1:], want[1:])
    assert err <= BF16_ATOL and not np.asarray(got[0]).any(), err
    assert np.array_equal(np.asarray(written[1]), np.asarray(scattered)) and np.array_equal(
        np.asarray(written[0]), np.asarray(stack[0]))
    say(f"  mla_attn decode step (depths {depths}, {h} heads x {lanes} lanes, values the first {r}, pages of {ps}, "
        f"{mode or 'compiled (Mosaic)'}): max|kernel-dense|={err:.4f}; the new entries written in place, layer 0 untouched")

    # a pack: slot 2 brings a block and a half behind cached entries, slot 0 one block behind none
    bt, cap = (32, 128) if on_chip else (8, 32)
    row_slot, row_pos = np.full(cap, -1, np.int32), np.full(cap, -1, np.int32)
    hist2 = 5 * ps + 3
    row_slot[:2 * bt], row_pos[:bt + bt // 2] = 2, np.arange(hist2, hist2 + bt + bt // 2)
    row_slot[2 * bt:3 * bt], row_pos[2 * bt:3 * bt - 3] = 0, np.arange(0, bt - 3)
    hist = jnp.asarray([0, 0, hist2, 0], jnp.int32)
    qp, newp = rand(key[3], 1, h, cap), rand(key[4], 1, 1, cap)
    kw = dict(page_table=table, row_slot=row_slot, row_pos=row_pos, slot_hist=hist, latent=r, sm_scale=scale,
              token_block=bt)
    pack = jax.jit(lambda st: A.ragged_latent_attention(qp, newp, st, impl=mode, layer=jnp.int32(1), **kw))
    got, written = jax.block_until_ready(pack(stack))
    want, payload = A.ragged_latent_attention(qp, newp, stack[1], impl="dense", **kw)
    err = err_of(got, want)
    assert err <= BF16_ATOL, err
    valid = row_pos >= 0
    there = np.asarray(table)[np.maximum(row_slot, 0), np.maximum(row_pos, 0) // ps]
    expect = np.asarray(stack[1]).copy()
    expect[there[valid], :, row_pos[valid] % ps] = np.asarray(payload)[valid]
    assert np.array_equal(np.asarray(written[1]), expect)
    say(f"  mla_prefill_attn pack ({cap} rows in blocks of {bt}, a slot behind {hist2} cached entries and one behind none, "
        f"{mode or 'compiled (Mosaic)'}): max|kernel-dense|={err:.4f}; the pack's entries written in place")
    if not on_chip:
        return

    # the two forms of a pack's attention, one slot's rows at three depths (host clock around 10 calls)
    wkv = (jax.random.normal(key[5], (r, h, n + dv)) * r ** -0.5).astype(jnp.bfloat16)
    long_table = jnp.tile(jnp.arange(1, pages, dtype=jnp.int32)[None, :24576 // ps + 16], (4, 1))

    def timed(fn, *args):
        jax.block_until_ready(fn(*args))
        t0 = time.perf_counter()
        for _ in range(10):
            out = fn(*args)
        jax.block_until_ready(out)
        return 1e3 * (time.perf_counter() - t0) / 10

    def absorbed_ms(rows, depth, block):
        """One slot's ``rows`` behind ``depth`` cached entries through the pack kernel, one layer's pages."""
        rs_, rp_ = np.zeros(rows, np.int32), np.arange(depth, depth + rows, dtype=np.int32)
        q1, new1 = rand(key[1], 1, h, rows), rand(key[2], 1, 1, rows)
        hist1 = jnp.asarray([depth, 0, 0, 0], jnp.int32)
        return q1, timed(jax.jit(lambda one_layer: A.ragged_latent_attention(
            q1, new1, one_layer, page_table=long_table, row_slot=rs_, row_pos=rp_, slot_hist=hist1, latent=r,
            sm_scale=scale, token_block=block)[0]), stack[1])

    for block in (16, 32, 64):  # the pack kernel's token block
        say(f"  token block {block}: 256 rows at depth 8192 absorbed in {absorbed_ms(256, 8192, block)[1]:.3f} ms")
    for rows in (64, 256):
        for depth in (2048, 8192, 24576):
            q1, t_abs = absorbed_ms(rows, depth, bt)

            def expand(st):  # the slot's cached entries into every head's keys and values
                ent = A.gather_kv_pages(st[1], long_table[:1, :depth // ps])[0, 0]      # [depth, lanes]
                kv = jnp.einsum("lr,rhd->hld", ent[:, :r], wkv)
                k = jnp.concatenate([kv[..., :n], jnp.broadcast_to(ent[None, :, r:r + p], (h, depth, p))], axis=-1)
                return k, kv[..., n:]

            def attend(k, v):  # rows x depth, every head its own keys and values (the pack's own rows left out)
                return A.mha_reference(q1[..., :n + p], k[None], v[None], sm_scale=scale)

            up = jax.jit(expand)
            k_, v_ = up(stack)
            t_up, t_att = timed(up, stack), timed(jax.jit(attend), k_, v_)
            pairs = rows * (depth + (rows + 1) / 2)
            say(f"  {rows} rows of one slot at depth {depth}: absorbed mla_prefill_attn {t_abs:.3f} ms "
                f"({1e-9 * pairs * h * 2 * (2 * r + p) / t_abs:.1f} TFLOP/s of its 2 x ({r + p} + {r}) a pair a head); "
                f"expanded: up-projection {t_up:.3f} ms + XLA's dense attention {t_att:.3f} ms = {t_up + t_att:.3f} ms "
                "(host clock, 10 calls each, dispatch included)")


# ---------------------------------------------------------------------------
# train
# ---------------------------------------------------------------------------


def init_variables(model_def, seed: int):
    import jax

    return model_def.init_variables(jax.random.PRNGKey(seed), batch_size=1, seq_len=128)


def train_batch(S: Sizes, cfg, seed: int):
    import numpy as np

    ids = np.random.RandomState(seed + 1).randint(0, cfg.vocab_size, (S.train_batch, S.seq_len))
    return {"input_ids": ids, "labels": ids}


def accelerator_train(S: Sizes, seed: int, on_chip: bool, sharding=None, variables=None):
    """The library's path. Returns (losses, compiled step text, accelerator, model)."""
    import jax
    import optax

    from accelerate_tpu import Accelerator, Model
    from accelerate_tpu.models import DecoderLM
    from accelerate_tpu.state import AcceleratorState

    AcceleratorState._reset_state(reset_partial_state=True)
    accelerator = Accelerator(mixed_precision="bf16", sharding_config=sharding)
    cfg = train_cfg(S)
    model_def = DecoderLM(cfg, mesh=accelerator.mesh)
    if variables is None:
        variables = init_variables(model_def, seed)
    say(f"  depth {cfg.num_layers} layers, {cfg.num_params / 1e9:.3f}B params, batch "
        f"{S.train_batch} x {S.seq_len} tokens, mesh {accelerator.state.mesh_shape}")
    model, _ = accelerator.prepare(Model(model_def, variables), optax.adamw(3e-4))
    del variables
    step = accelerator.build_train_step()
    batch = accelerator.prepare_for_eval(train_batch(S, cfg, seed))

    losses, walls = [], []

    def one_step():
        t0 = time.perf_counter()
        loss = float(jax.block_until_ready(step(batch)["loss"]))
        walls.append(time.perf_counter() - t0)
        losses.append(loss)
        say(f"  step {len(losses)}: loss {loss:.4f}  wall {walls[-1]:.3f}s")

    one_step()
    # the same program again through the AOT path (served by the persistent
    # cache the call above filled) to read its text
    (spec,) = accelerator.audit_entrypoints(step, batch)
    text = spec["fn"].lower(*spec["args"]).compile().as_text()
    assert_kernel_in(text, "train step (flash attention)", on_chip)
    with steady_window("after step 1"):
        for _ in range(S.train_steps - 1):
            one_step()
    if not all(math.isfinite(l) for l in losses):
        raise AssertionError(f"non-finite loss: {losses}")
    if not losses[-1] < losses[0]:
        raise AssertionError(f"loss did not fall on a fixed batch: {losses}")
    steady_wall = sorted(walls[1:])[len(walls[1:]) // 2]
    say(f"  compile+first step {walls[0]:.1f}s, steady median {steady_wall:.3f}s/step, "
        f"0 compile events after step 1")
    return losses, text, accelerator, model


# ---------------------------------------------------------------------------
# serve
# ---------------------------------------------------------------------------


def serve_params(model_def, seed: int):
    """Seeded weights in the serving dtype, made on the device in one program."""
    import jax
    import jax.numpy as jnp

    from accelerate_tpu.parallel.sharding import unbox_params

    dt = model_def.config.dtype

    @jax.jit
    def init(key):
        params, _ = unbox_params(model_def.init(key, jnp.zeros((1, 8), jnp.int32))["params"])
        return jax.tree_util.tree_map(
            lambda x: x.astype(dt) if jnp.issubdtype(x.dtype, jnp.floating) else x, params)

    return init(jax.random.PRNGKey(seed))


def serve_prompts(S: Sizes, vocab: int, seed: int) -> list:
    import numpy as np

    rng = np.random.RandomState(seed + 2)
    prompts = [rng.randint(0, vocab, (n,)).astype(np.int32) for n in S.prompt_lens]
    shared = prompts[-1][: S.prompt_lens[-1] // 3]
    for extra in (S.prompt_lens[0], S.prompt_lens[1]):  # two more behind one prefix
        prompts.append(np.concatenate([shared, rng.randint(0, vocab, (extra,)).astype(np.int32)]))
    return prompts


def run_engine(S: Sizes, cfg, params, prompts, on_chip: bool, label: str, **engine_kw):
    """warmup -> submit -> run on one engine. Returns the generated tokens."""
    import jax

    from accelerate_tpu.models import DecoderLM
    from accelerate_tpu.ops.attention import decode_kernel_active, prefill_kernel_active
    from accelerate_tpu.serving import ServingEngine

    t0 = time.perf_counter()
    engine = ServingEngine(
        DecoderLM(cfg), params, page_size=S.page_size, num_slots=S.num_slots,
        max_cache_len=S.max_cache_len, **engine_kw,
    )
    engine.warmup()
    warm = time.perf_counter() - t0
    say(f"  [{label}] engine + warmup (all compiles) {warm:.1f}s, "
        f"KV arena {engine.arena_bytes / 2**30:.2f} GiB in {engine.num_pages} pages")
    paged_cfg = dataclasses.replace(
        cfg, kv_page_size=S.page_size, kv_num_pages=engine.num_pages)
    kernels_on = (decode_kernel_active(paged_cfg), prefill_kernel_active(paged_cfg))
    if label == "kernel":
        if kernels_on != (True, True):
            raise AssertionError(f"decode/prefill kernel gates are {kernels_on}, want both on")
        say("  decode_kernel_active and prefill_kernel_active: True")
        for spec in engine.audit_entrypoints():
            if spec["name"] == "decode_step" or spec["name"].startswith("ragged_prefill_"):
                text = spec["fn"].lower(*spec["args"], **spec.get("kwargs", {})).compile().as_text()
                assert_kernel_in(text, spec["name"], on_chip)
    elif kernels_on != (False, False):
        raise AssertionError(f"the dense engine resolved to kernels: {kernels_on}")

    engine.mark_steady()
    t0 = time.perf_counter()
    with steady_window("after warmup()"):
        reqs = [engine.submit(p, max_new_tokens=S.new_tokens, seed=i) for i, p in enumerate(prompts)]
        engine.run()
    wall = time.perf_counter() - t0
    if engine.admission_recompiles:
        raise AssertionError(f"admission_recompiles = {engine.admission_recompiles}")
    for r in reqs:
        if r.outcome != "finished" or len(r.tokens) != S.new_tokens:
            raise AssertionError(
                f"request {r.id}: outcome {r.outcome}, {len(r.tokens)}/{S.new_tokens} tokens")
        ttft = (r.first_token_t - r.submit_t) if r.first_token_t else float("nan")
        say(f"  [{label}] request {r.id}: prompt {len(r.prompt)}, prefix hit {r.prefix_hit}, "
            f"prefill {r.prefill_kernel}, first token after {ttft:.3f}s, {len(r.tokens)} tokens")
    total = sum(len(r.tokens) for r in reqs)
    say(f"  [{label}] {len(reqs)} requests, {total} tokens in {wall:.2f}s after warm-up "
        f"({engine.step_count} engine steps), 0 compile events after warmup()")
    tokens = [list(r.tokens) for r in reqs]
    del engine, reqs
    gc.collect()
    jax.clear_caches()
    return tokens


def serve_phase(S: Sizes, seed: int, on_chip: bool) -> None:
    import jax.numpy as jnp
    import numpy as np

    from accelerate_tpu.models import DecoderLM

    cfg = llama_cfg(S, S.serve_layers, remat=False, decode_kernel=S.kernel_mode,
                    prefill_kernel=S.kernel_mode)
    params = serve_params(DecoderLM(cfg), seed)
    say(f"  depth {cfg.num_layers} layers, {cfg.num_params / 1e9:.3f}B params in "
        f"{cfg.dtype.__name__}, page size {S.page_size}, {S.num_slots} slots x {S.max_cache_len}")
    prompts = serve_prompts(S, cfg.vocab_size, seed)
    kernel = run_engine(S, cfg, params, prompts, on_chip, "kernel")
    dense_cfg = dataclasses.replace(cfg, decode_kernel="dense", prefill_kernel="dense")
    # the packed dispatch's dense reference gathers the cache of every row's
    # slot: at these widths a grid of 256 rows holds 8.5 GiB of temporaries
    # (ahead-of-time compile for v5e:2x2, PR 30), one of 64 rows 4.4 GiB
    dense = run_engine(S, dense_cfg, params, prompts, on_chip, "dense", prefill_chunks=(64,))
    same = sum(a == b for k, d in zip(kernel, dense) for a, b in zip(k, d))
    total = sum(len(k) for k in kernel)
    firsts = [k[0] == d[0] for k, d in zip(kernel, dense)]
    say(f"  greedy tokens, kernel engine vs dense engine: {same}/{total} agree "
        f"({same / total:.1%}); first token agrees on {sum(firsts)}/{len(firsts)} requests")
    # Random weights give near-flat logits over the vocabulary, so the largest
    # one can change on rounding. A first token may differ only where the
    # plain forward pass itself cannot order the two candidates: their logits
    # no further apart than one bf16 step at the largest logit's magnitude.
    model_def = DecoderLM(cfg)
    for i, (k, d) in enumerate(zip(kernel, dense)):
        if k[0] == d[0]:
            continue
        logits = np.asarray(
            model_def.apply({"params": params}, jnp.asarray(prompts[i])[None])["logits"][0, -1],
            np.float32)
        gap = abs(logits[k[0]] - logits[d[0]])
        tie = BF16_STEP * 2.0 ** math.floor(math.log2(np.abs(logits).max()))
        say(f"  request {i}: first token {k[0]} (kernel) vs {d[0]} (dense); plain-forward logits "
            f"{logits[k[0]]:.4f} vs {logits[d[0]]:.4f} (largest {logits.max():.4f}), "
            f"gap {gap:.4f}, one bf16 step {tie:.4f}")
        if gap > tie:
            raise AssertionError(f"request {i}: first tokens differ and it is not a tie")


# ---------------------------------------------------------------------------
# four chips: the sharded trainer against a one-device run
# ---------------------------------------------------------------------------


def reference_train(S: Sizes, cfg, variables, batch) -> list:
    """Plain jax + optax on ONE device: same weights, batch, optimizer and
    bf16-compute / fp32-master recipe, none of the library's train engine."""
    import jax
    import jax.numpy as jnp
    import optax

    from accelerate_tpu.models import DecoderLM
    from accelerate_tpu.parallel.sharding import unbox_params

    model_def = DecoderLM(cfg)  # no mesh
    dev = jax.devices()[0]
    params = jax.device_put(unbox_params(variables["params"])[0], dev)
    tx = optax.adamw(3e-4)
    opt_state = tx.init(params)
    ids = jax.device_put(jnp.asarray(batch["input_ids"]), dev)

    def step(params, opt_state, ids):
        def loss_fn(p):
            p16 = jax.tree_util.tree_map(lambda x: x.astype(jnp.bfloat16), p)
            return model_def.apply({"params": p16}, ids, labels=ids)["loss"].astype(jnp.float32)

        loss, grads = jax.value_and_grad(loss_fn)(params)
        updates, opt_state = tx.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    step = jax.jit(step, donate_argnums=(0, 1))
    losses = []
    for i in range(S.train_steps):
        params, opt_state, loss = step(params, opt_state, ids)
        losses.append(float(jax.block_until_ready(loss)))
        say(f"  one-device step {i + 1}: loss {losses[-1]:.4f}")
    return losses


def check_placement(model, devices) -> None:
    """Every parameter is addressable on all four devices, every sharded one
    as four distinct quarter-size shards; what stays replicated (norm
    scales) is a negligible share, so each device holds about a quarter."""
    import jax

    per_device = {d: 0 for d in devices}
    total = replicated = n_sharded = 0
    for path, leaf in jax.tree_util.tree_flatten_with_path(model.params)[0]:
        name = jax.tree_util.keystr(path)
        shards = leaf.addressable_shards
        on = {s.device for s in shards}
        if on != set(devices):
            raise AssertionError(f"{name} lives on {len(on)} of {len(devices)} devices")
        total += leaf.nbytes
        for s in shards:
            per_device[s.device] += s.data.nbytes
        if leaf.sharding.is_fully_replicated:
            replicated += leaf.nbytes
            continue
        n_sharded += 1
        distinct = {tuple((sl.start, sl.stop) for sl in s.index) for s in shards}
        frac = shards[0].data.nbytes / leaf.nbytes
        if len(distinct) != len(devices) or frac != 1 / len(devices):
            raise AssertionError(
                f"{name} {leaf.shape} {leaf.sharding.spec}: {len(distinct)} distinct shards of "
                f"{frac:.3f} of the bytes, want {len(devices)} of {1 / len(devices):.3f}")
    held = [b / total for b in per_device.values()]
    say(f"  parameters: {total / 2**30:.3f} GiB in {n_sharded} sharded leaves, each as "
        f"{len(devices)} distinct shards of 1/{len(devices)} on {len(devices)} devices; replicated "
        f"leaves hold {replicated / total:.2%}; share of the bytes on each device "
        f"{[f'{h:.3f}' for h in held]}")
    if max(held) > 0.26:
        raise AssertionError(f"a device holds {max(held):.3f} of the parameter bytes, want about 1/4")


def four_chip_phase(S: Sizes, seed: int, on_chip: bool) -> None:
    import jax

    from accelerate_tpu.models import DecoderLM
    from accelerate_tpu.utils.dataclasses import ShardingConfig

    cfg = train_cfg(S)
    # one set of seeded fp32 weights, on the host, for both runs
    variables = jax.device_get(init_variables(DecoderLM(cfg), seed))
    ref = reference_train(S, cfg, variables, train_batch(S, cfg, seed))
    gc.collect()
    jax.clear_caches()

    losses, text, accelerator, model = accelerator_train(
        S, seed, on_chip, sharding=ShardingConfig(fsdp=2, tensor_parallel=2), variables=variables)
    check_placement(model, jax.devices())
    found = {op: len(re.findall(rf"\b{op}(?:-start)?\(", text))
             for op in ("all-reduce", "all-gather", "reduce-scatter", "collective-permute", "all-to-all")}
    say(f"  collectives in the compiled sharded step: {found}")
    if not sum(found.values()):
        raise AssertionError("no collective in the compiled sharded step")
    say(f"  loss, one device: {[round(l, 4) for l in ref]}")
    say(f"  loss, fsdp2 x tp2: {[round(l, 4) for l in losses]}")
    if abs(ref[0] - losses[0]) > BF16_ATOL:
        raise AssertionError(f"first-step loss differs: {ref[0]} vs {losses[0]}")
    # the trajectories track: every step inside bf16 tolerance of the one-device
    # loss (absolute + relative, as allclose; late losses on a memorised batch
    # are near zero, where a purely relative bound means nothing)
    drift = [abs(a - b) for a, b in zip(ref, losses)]
    say(f"  |one device - sharded| per step: {[round(x, 5) for x in drift]}")
    bad = [i + 1 for i, (a, x) in enumerate(zip(ref, drift)) if x > BF16_ATOL + BF16_RTOL * abs(a)]
    if bad:
        raise AssertionError(f"loss trajectories drift apart at steps {bad}")


# ---------------------------------------------------------------------------


def main() -> int:
    global _PREFIX
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1,
                        help="4: only the sharded trainer and its one-device comparison")
    parser.add_argument("--cpu-rehearsal", action="store_true",
                        help="tiny widths on the CPU, kernels interpreted; never a result")
    parser.add_argument("--phase", default=None,
                        help="run only the phases whose name contains this (the last line then says so)")
    args = parser.parse_args()
    if args.cpu_rehearsal:
        _PREFIX = "[CPU REHEARSAL, not a chip run] "
        os.environ["JAX_PLATFORMS"] = "cpu"
        if args.chips > 1:
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "")
                + f" --xla_force_host_platform_device_count={args.chips}")
    t_start = time.perf_counter()

    import jax

    devices = jax.devices()
    dev = devices[0]
    want = "cpu" if args.cpu_rehearsal else "tpu"
    if dev.platform != want or len(devices) != args.chips:
        print(f"chip_smoke: found {len(devices)} x {dev.platform} ({dev.device_kind}), need "
              f"{args.chips} x {want}; there is no fallback "
              "(--cpu-rehearsal is the tiny CPU run and says so on every line)", file=sys.stderr)
        return 2
    on_chip = not args.cpu_rehearsal
    S = REAL if on_chip else TINY

    import flax
    import jaxlib
    import optax

    from accelerate_tpu.runtime.native import native_available
    from accelerate_tpu.utils.compile_cache import (
        ensure_persistent_compile_cache,
        install_compile_listeners,
    )

    install_compile_listeners()
    cache_dir = ensure_persistent_compile_cache()
    from importlib import metadata

    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:  # a CPU-only image; only printed
        libtpu = "not installed"
    say(f"python {sys.version.split()[0]}  jax {jax.__version__}  jaxlib {jaxlib.__version__}  "
        f"libtpu {libtpu}  flax {flax.__version__}  optax {optax.__version__}")
    say(f"device: {len(devices)} x {dev.platform} ({dev.device_kind}); seed {args.seed}")
    cached = len(os.listdir(cache_dir)) if cache_dir else 0
    say(f"compile cache: {cache_dir} ({cached} entries at start; "
        f"JAX_COMPILATION_CACHE_DIR {'set' if os.environ.get('JAX_COMPILATION_CACHE_DIR') else 'unset'})")
    t0 = time.perf_counter()
    say(f"csrc/att_runtime.cpp native library loaded: {native_available()} "
        f"({time.perf_counter() - t0:.1f}s incl. build on first use)")

    phases = (
        [("four chips: fsdp2 x tp2 trainer vs one device", four_chip_phase)]
        if args.chips == 4 else
        [("kernels vs references", kernels_phase), ("state-space scan vs reference", ssm_phase),
         ("closing-window pooling vs reference", eva_phase),
         ("latent attention's kernels vs their dense reads", latent_phase),
         ("ssd: the recurrence with heads and two-matrix experts vs their jax.numpy reads", ssd_phase),
         ("gdn: the delta rule's two forms and many narrow experts vs their jax.numpy reads", gdn_phase),
         ("train", accelerator_train), ("serve", serve_phase)]
    )
    if args.phase:
        phases = [(name, fn) for name, fn in phases if args.phase in name]
        if not phases:
            parser.error(f"no phase's name contains {args.phase!r}")
    for name, fn in phases:
        say(f"== {name}")
        before, t0 = compile_counts(), time.perf_counter()
        fn(S, args.seed, on_chip)
        after = compile_counts()
        say(f"== {name}: ok in {time.perf_counter() - t0:.1f}s "
            f"(compile events {after['count'] - before['count']}, {after['seconds'] - before['seconds']:.1f}s "
            f"in them, persistent-cache hits {after['cache_hits'] - before['cache_hits']}); "
            f"peak device memory {peak_memory(dev)}")
        gc.collect()
        jax.clear_caches()

    counts = compile_counts()
    say(f"total wall {time.perf_counter() - t_start:.1f}s; compile events {counts['count']} "
        f"({counts['seconds']:.1f}s), persistent-cache hits {counts['cache_hits']}")
    say(json.dumps({"ok": True, **({"only": [name for name, _ in phases]} if args.phase else {}), "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

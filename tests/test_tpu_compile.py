"""The main path's kernels, compiled by the chip's own compiler (Mosaic) for
a described ``v5e:2x2`` topology at real widths — no chip attached, nothing
runs. Interpret mode hid two refused kernel families for three PRs (GQA
ragged prefill, every int4 variant); these compiles are what would have
caught them, at about two seconds each. A compile that passes is not a chip
run: it says the compiler accepts the kernel, nothing about results or time.
What the compiler refuses is held by name too, with the gate that keeps it off
the kernel (``REFUSED``).
"""

import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # or the compiler logs under /tmp

import jax
import jax.numpy as jnp
import pytest

import accelerate_tpu.ops.attention as A

SLOTS, PAGES, SM_SCALE = 8, 512, 0.088


@pytest.fixture(scope="module")
def v5e():
    """The four described devices of a v5e:2x2; the persistent cache is off
    around these compiles (an entry written for a described chip cannot be
    read back without one, and the next run would warn)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no libtpu on this image: nothing to compile with
        pytest.skip(f"cannot describe a v5e:2x2 topology here: {e}")
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo.devices
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def chip(v5e):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(v5e[0])


def _flash(chip, *, b, h, kvh, s, d, mask=None, grad=True):
    S = lambda shape, dt=jnp.bfloat16: jax.ShapeDtypeStruct(shape, dt, sharding=chip)
    rows = {
        None: {},
        "kv_mask": {"kv_mask": S((b, s), jnp.int32)},
        "segments": {"q_segment_ids": S((b, s), jnp.int32),
                     "kv_segment_ids": S((b, s), jnp.int32)},
    }[mask]

    def fwd(q, k, v, rows):
        return A.flash_attention(q, k, v, causal=True, **rows)

    def fwd_bwd(q, k, v, rows):
        return jax.grad(lambda q, k, v: fwd(q, k, v, rows).astype(jnp.float32).sum(),
                        argnums=(0, 1, 2))(q, k, v)

    return fwd_bwd if grad else fwd, (S((b, h, s, d)), S((b, kvh, s, d)), S((b, kvh, s, d)), rows)


def _kv_operands(chip, shape, d, bits):
    """Payload (+ scale) specs of a K or V arena at ``bits`` storage."""
    S = lambda shp, dt: jax.ShapeDtypeStruct(shp, dt, sharding=chip)
    width = d // 2 if bits == 4 else d
    payload = S((*shape, width), jnp.int8 if bits else jnp.bfloat16)
    return payload, (S((*shape, 1), jnp.float32) if bits else None)


def _paged_decode(chip, *, h=32, kvh=32, d=128, ps=16, sq=1, bits=0, slots=SLOTS, pages=PAGES,
                  table=None, dv=None, window=None, sink=False, layers=2, write=False):
    """The kernel over the layers' stack and a layer index; ``write``: it also
    puts each slot's new row into its page and the stack is its output."""
    S = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=chip)
    kp, ks = _kv_operands(chip, (layers, pages, kvh, ps), d, bits)
    vp, _ = _kv_operands(chip, (layers, pages, kvh, ps), dv or d, bits)
    new = lambda width: S((slots, kvh, 1, width), jnp.bfloat16) if write else None

    def fn(q, kp, vp, table, pos, lengths, layer, ks, vs, sink, k_new, v_new):
        return A._paged_decode_kernel_call(
            q, kp, vp, table, pos, lengths, SM_SCALE, False, k_scale=ks, v_scale=vs, quant_bits=bits,
            window=window, sink=sink, value_scale=0.707 if window else 1.0, layer=layer,
            k_new=k_new, v_new=v_new)

    return fn, (S((slots, h, sq, d), jnp.bfloat16), kp, vp,
                S((slots, table or 2048 // ps), jnp.int32), S((slots, sq), jnp.int32),
                S((slots,), jnp.int32), S((), jnp.int32), ks, ks, S((h,), jnp.float32) if sink else None,
                new(d), new(dv or d))


def _dense_decode(chip, *, bits=0):
    S = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=chip)
    k, ks = _kv_operands(chip, (SLOTS, 32, 2048), 128, bits)

    def fn(q, k, v, pos, ks, vs):
        return A._dense_decode_kernel_call(
            q, k, v, pos, SM_SCALE, 256, False, k_scale=ks, v_scale=vs, quant_bits=bits)

    return fn, (S((SLOTS, 32, 1, 128), jnp.bfloat16), k, k, S((SLOTS, 1), jnp.int32), ks, ks)


def _ragged_prefill(chip, *, h=32, kvh=32, d=128, ps=16, bits=0, cap=256, bt=8, dv=None, window=None,
                    sink=False, table=None, slots=SLOTS, pages=PAGES, layers=None):
    """One layer's pages, the pack's payloads returned for a scatter; with
    ``layers`` the layers' stack and a layer index: the kernel writes the
    pack's rows into the slots' pages and the stack is its output."""
    S = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=chip)
    arena = (pages, kvh, ps) if layers is None else (layers, pages, kvh, ps)
    kp, ks = _kv_operands(chip, arena, d, bits)
    vp, _ = _kv_operands(chip, arena, dv or d, bits)
    rows = S((cap,), jnp.int32)

    def fn(q, kn, vn, kp, vp, table, row_slot, row_pos, hist, ks, vs, sink, layer):
        return A._ragged_prefill_kernel_call(
            q, kn, vn, kp, vp, table, row_slot, row_pos, hist, SM_SCALE, bt, False,
            k_scale=ks, v_scale=vs, quant_bits=bits, window=window, sink=sink,
            value_scale=0.707 if window else 1.0, layer=layer)

    return fn, (S((1, h, cap, d), jnp.bfloat16), S((1, kvh, cap, d), jnp.bfloat16),
                S((1, kvh, cap, dv or d), jnp.bfloat16), kp, vp,
                S((slots, table or 2048 // ps), jnp.int32), rows, rows, S((slots,), jnp.int32), ks, ks,
                S((h,), jnp.float32) if sink else None, None if layers is None else S((), jnp.int32))


def _latent_decode(chip, *, h=64, lanes=640, latent=512, ps=16, slots=16, pages=16384, table=1600, layers=5,
                   write=True):
    """Latent attention's decode step at the published widths (the
    gigachat3 cell's shape): 64 absorbed query heads against one 640-lane
    entry a token, the expert run's five layers' stack and a layer index."""
    S = lambda shape, dt=jnp.bfloat16: jax.ShapeDtypeStruct(shape, dt, sharding=chip)

    def fn(q, pages_, table_, pos, lengths, layer, new):
        return A._paged_decode_kernel_call(
            q, pages_, None, table_, pos, lengths, SM_SCALE, False, layer=layer, k_new=new, latent=latent)

    return fn, (S((slots, h, 1, lanes)), S((layers, pages, 1, ps, lanes)), S((slots, table), jnp.int32),
                S((slots, 1), jnp.int32), S((slots,), jnp.int32), S((), jnp.int32),
                S((slots, 1, 1, lanes)) if write else None)


def _latent_prefill(chip, *, h=64, lanes=640, latent=512, ps=16, cap=256, bt=32, slots=16, pages=16384,
                    table=1600, layers=5):
    """Latent attention's pack at the published widths: the rows' absorbed
    queries and their own entries, the layers' stack written in place."""
    S = lambda shape, dt=jnp.bfloat16: jax.ShapeDtypeStruct(shape, dt, sharding=chip)
    rows = S((cap,), jnp.int32)

    def fn(q, new, pages_, table_, row_slot, row_pos, hist, layer):
        return A._ragged_prefill_kernel_call(
            q, new, None, pages_, None, table_, row_slot, row_pos, hist, SM_SCALE, bt, False,
            layer=layer, latent=latent)

    return fn, (S((1, h, cap, lanes)), S((1, 1, cap, lanes)), S((layers, pages, 1, ps, lanes)),
                S((slots, table), jnp.int32), rows, rows, S((slots,), jnp.int32), S((), jnp.int32))


def _moe_experts(chip, *, rows, layers=4, held=16, d=4096, m=2048):
    """The MiMo cell's window run: the four layers' stacks and a layer index."""
    from accelerate_tpu.models import moe

    S = lambda shape, dt=jnp.bfloat16: jax.ShapeDtypeStruct(shape, dt, sharding=chip)
    fn = lambda x, wg, wu, wd, sizes, layer: moe._experts_kernel_call(x, wg, wu, wd, sizes, layer, False)
    return fn, (S((rows, d)), S((layers, held, d, m)), S((layers, held, d, m)), S((layers, held, m, d)),
                S((held,), jnp.int32), S((), jnp.int32))


def _ssm_scan(chip, *, blocks, rows, width=5120, n=16, layers=13, slots=128):
    """The selective-scan kernel over the layers' stack of states and a layer
    index, the stack its output: ``blocks`` of ``rows`` rows (a pack's token
    blocks, or a decode step's one row a slot)."""
    from accelerate_tpu.ops import ssm

    S = lambda shape, dt=jnp.float32: jax.ShapeDtypeStruct(shape, dt, sharding=chip)
    fn = lambda u, dt, b, c, a, d, state, slot, n_rows, fresh, layer: ssm._ssm_scan_call(
        u, dt, b, c, a, d, state, slot, n_rows, fresh, layer, False)
    per_block = S((blocks,), jnp.int32)
    return fn, (S((blocks, rows, width), jnp.bfloat16), S((blocks, rows, width)), S((blocks, rows, n)),
                S((blocks, rows, n)), S((n, width)), S((width,)), S((layers, slots, n, width)),
                per_block, per_block, per_block, S((), jnp.int32))
def _ssd_scan(chip, *, blocks, rows, heads=128, head_dim=64, groups=8, n=128, layers=3, slots=96):
    """The recurrence with heads over the layers' stack of states and a layer
    index, the stack its output, at the nemotron3 cell's published shapes: a
    slot's state in a layer is 64 chunks of [128, 128] float32, 4 MB."""
    from accelerate_tpu.ops import ssm

    S = lambda shape, dt=jnp.float32: jax.ShapeDtypeStruct(shape, dt, sharding=chip)
    width = heads * head_dim
    fn = lambda u, dt, b, c, a, d, state, slot, n_rows, fresh, layer: ssm._ssd_scan_call(
        u, dt, b, c, a, d, state, slot, n_rows, fresh, layer, False)
    per_block = S((blocks,), jnp.int32)
    return fn, (S((blocks, rows, width)), S((blocks, rows, width)), S((blocks, rows, groups, n)),
                S((blocks, rows, groups, n)), S((width,)), S((width,)),
                S((layers, slots, *ssm.ssd_state_shape(width, groups, n))),
                per_block, per_block, per_block, S((), jnp.int32))


def _gdn_scan(chip, *, blocks, rows, hk=16, hv=32, dk=128, dv=128, layers=3, slots=128):
    """The delta rule's kernel over the layers' stack of states and a layer
    index, the stack its output, at the qwen3-next cell's published shapes (a
    slot's state in a layer is 32 heads of [128, 128] float32, 2 MB)."""
    from accelerate_tpu.ops import ssm

    S = lambda shape, dt=jnp.float32: jax.ShapeDtypeStruct(shape, dt, sharding=chip)
    fn = lambda q, k, v, g, beta, state, slot, n_rows, fresh, layer: ssm._gdn_scan_call(
        q, k, v, g, beta, state, slot, n_rows, fresh, layer, False)
    per_block = S((blocks,), jnp.int32)
    return fn, (S((blocks, rows, hk, dk)), S((blocks, rows, hk, dk)), S((blocks, rows, hv, dv)),
                S((blocks, rows, hv)), S((blocks, rows, hv)), S((layers, slots, hv, dk, dv)),
                per_block, per_block, per_block, S((), jnp.int32))


def _moe_experts_relu2(chip, *, rows, layers=3, held=128, d=1024, m=2688):
    """The two-matrix experts' kernel out of a run's stacks (the nemotron3
    cell's run of three expert layers, 128 held experts in a 1,024-wide
    latent) at a decode step's and a pack's rows."""
    from accelerate_tpu.models import moe

    S = lambda shape, dt=jnp.bfloat16: jax.ShapeDtypeStruct(shape, dt, sharding=chip)
    fn = lambda x, wu, wd, sizes, layer: moe._experts2_kernel_call(x, wu, wd, sizes, layer, False)
    # (float32 rows, as the latent projection hands them: the kernel multiplies them in two terms)
    return fn, (S((rows, d), jnp.float32), S((layers, held, d, m)), S((layers, held, m, d)), S((held,), jnp.int32),
                S((), jnp.int32))


def _eva_pool(chip, *, steps, kvh=32, d=128, ps=16, layers=8, pages=1792):
    """The pooling kernel of a closing window (ops/eva.py) at published widths:
    ``steps`` pages pooled in one layer of the layers' stack (a decode step's
    carried arena), the stack aliased to its output."""
    from accelerate_tpu.ops import eva

    S = lambda shape, dt=jnp.bfloat16: jax.ShapeDtypeStruct(shape, dt, sharding=chip)
    arena = (layers, pages, kvh, ps, d)
    fn = lambda k, v, mu, phi, src, dst, off, layer: eva._eva_pool_kernel_call(
        k, v, mu, phi, src, dst, off, layer, SM_SCALE, False)
    per_step = S((steps,), jnp.int32)
    return fn, (S(arena), S(arena), S((kvh, d), jnp.float32), S((kvh, d), jnp.float32),
                per_step, per_step, per_step, S((), jnp.int32))


CASES = {
    # latent attention read absorbed, the gigachat3 cell's shapes
    # (benchmarks/configs/gigachat3.1-702b-serve-6l-ep32.json): the entry is
    # 576 values stored in 640 lanes, its first 512 the value
    "latent_decode_in_place": (_latent_decode, dict()),
    "latent_decode_one_layer_no_write": (_latent_decode, dict(layers=1, write=False)),
    "latent_prefill_pack_256_rows_in_place": (_latent_prefill, dict()),
    "latent_prefill_pack_64_rows_in_place": (_latent_prefill, dict(cap=64)),
    # flash attention, forward + backward (what the train step holds)
    "flash_mha_d128_fwd_bwd": (_flash, dict(b=1, h=32, kvh=32, s=2048, d=128)),
    "flash_gqa_32q8kv_fwd_bwd": (_flash, dict(b=1, h=32, kvh=8, s=2048, d=128)),
    "flash_d64_kv_mask_fwd_bwd": (_flash, dict(b=8, h=12, kvh=12, s=512, d=64, mask="kv_mask")),
    "flash_d64_segment_ids_fwd_bwd": (_flash, dict(b=8, h=12, kvh=12, s=512, d=64, mask="segments")),
    "flash_32k_context_fwd": (_flash, dict(b=1, h=8, kvh=8, s=32768, d=128, grad=False)),
    # paged decode, query width 1 = decode, 5 = several rows a slot (ROADMAP R12); the serving cells'
    # own shape (benchmarks/configs/mistral-7b-v0.3-serve-16l.json)
    "paged_decode_bf16_d128_sq1": (_paged_decode, dict(sq=1)),
    "paged_decode_bf16_d128_sq5": (_paged_decode, dict(sq=5)),
    "paged_decode_bf16_gqa_32q8kv_sq5": (_paged_decode, dict(kvh=8, sq=5)),
    "paged_decode_bf16_page8": (_paged_decode, dict(kvh=8, ps=8)),
    "paged_decode_serving_cell": (_paged_decode, dict(kvh=8, slots=32, pages=3584, table=256)),
    # the decode step's own form: the kernel writes the new rows, the stack aliased to its output
    "paged_decode_serving_cell_in_place": (
        _paged_decode, dict(kvh=8, slots=32, pages=3584, table=256, layers=16, write=True)),
    "paged_decode_bf16_page8_in_place": (_paged_decode, dict(kvh=8, ps=8, write=True)),
    # ragged prefill, the walk over a slot's live pages out of HBM, with
    # quantize-on-write: MHA and GQA x KV storage (scale pages, a 64-wide head
    # and an int4 payload go in as lane-dense views: _ragged_prefill_kernel_call)
    **{
        f"ragged_prefill_{name}_{kv}": (_ragged_prefill, dict(kvh=kvh, bits=bits))
        for name, kvh in (("mha", 32), ("gqa_32q8kv", 8))
        for kv, bits in (("bf16", 0), ("int8", 8), ("int4", 4))
    },
    # the token block a serving engine's default capacities give (64 rows)
    "ragged_prefill_gqa_32q8kv_bf16_block64": (_ragged_prefill, dict(kvh=8, bt=64)),
    "ragged_prefill_gqa_32q8kv_int8_block64": (_ragged_prefill, dict(kvh=8, bits=8, bt=64)),
    "ragged_prefill_mha_int4_block64": (_ragged_prefill, dict(bits=4, bt=64)),
    "ragged_prefill_mha_d64": (_ragged_prefill, dict(h=12, kvh=12, d=64)),
    "ragged_prefill_gqa_d64_int8": (_ragged_prefill, dict(h=12, kvh=4, d=64, bits=8)),
    "ragged_prefill_gqa_int4_page128": (_ragged_prefill, dict(kvh=8, ps=128, bits=4)),
    # the Mistral serving cells' own shape (benchmarks/configs/mistral-7b-v0.3-serve-16l.json),
    # at both capacities the engine compiles
    "ragged_prefill_serving_cell": (
        _ragged_prefill, dict(kvh=8, bt=64, slots=32, pages=3584, table=256)),
    "ragged_prefill_serving_cell_64_rows": (
        _ragged_prefill, dict(kvh=8, bt=64, cap=64, slots=32, pages=3584, table=256)),
    # the pack program's own form: the kernel writes the pack's pages, the stack aliased to its output
    "ragged_prefill_serving_cell_in_place": (
        _ragged_prefill, dict(kvh=8, bt=64, slots=32, pages=3584, table=256, layers=16)),
    "ragged_prefill_serving_cell_64_rows_in_place": (
        _ragged_prefill, dict(kvh=8, bt=64, cap=64, slots=32, pages=3584, table=256, layers=16)),
    "ragged_prefill_bf16_block8_page8_in_place": (_ragged_prefill, dict(kvh=8, ps=8, layers=2)),
    # ... and with --kv-cache-dtype int8 / int4 (no cell yet: ROADMAP R9)
    "ragged_prefill_serving_cell_int8": (
        _ragged_prefill, dict(kvh=8, bt=64, slots=32, pages=3584, table=256, bits=8)),
    "ragged_prefill_serving_cell_int4": (
        _ragged_prefill, dict(kvh=8, bt=64, slots=32, pages=3584, table=256, bits=4)),
    # layer kinds (benchmarks/configs/mimo-v2-flash-serve-7l-ep16.json): 64 query heads,
    # keys 192 wide stored padded to 256 lanes, values 128; a full kind of 4 kv heads,
    # a window kind of 8 with a window of 128 and a sink; the held experts' kernel
    "paged_decode_keys256_values128_full_kind": (
        _paged_decode, dict(h=64, kvh=4, d=256, dv=128, slots=64, pages=16384, table=512)),
    "paged_decode_keys256_values128_window_kind": (
        _paged_decode, dict(h=64, kvh=8, d=256, dv=128, slots=64, pages=1024, table=512, window=128, sink=True)),
    "paged_decode_keys256_values128_full_kind_in_place": (
        _paged_decode, dict(h=64, kvh=4, d=256, dv=128, slots=64, pages=16384, table=512, write=True)),
    "paged_decode_keys256_values128_window_kind_in_place": (
        _paged_decode, dict(h=64, kvh=8, d=256, dv=128, slots=64, pages=1024, table=512, window=128, sink=True,
                            layers=4, write=True)),
    "ragged_prefill_keys256_values128_full_kind": (
        _ragged_prefill, dict(h=64, kvh=4, d=256, dv=128, bt=64, table=512)),
    "ragged_prefill_keys256_values128_window_kind": (
        _ragged_prefill, dict(h=64, kvh=8, d=256, dv=128, bt=64, table=512, window=128, sink=True)),
    # keys 192 wide as they are (a model stores them at 256 for the decode kernel's sake)
    "ragged_prefill_keys192_values128_window_kind": (
        _ragged_prefill, dict(h=64, kvh=8, d=192, dv=128, bt=64, table=512, window=128, sink=True)),
    # ... and at the MiMo cell's slots and pools
    "ragged_prefill_mimo_cell_full_kind": (
        _ragged_prefill, dict(h=64, kvh=4, d=256, dv=128, bt=64, slots=64, pages=16384, table=512)),
    "ragged_prefill_mimo_cell_window_kind": (
        _ragged_prefill, dict(h=64, kvh=8, d=256, dv=128, bt=64, slots=64, pages=1024, table=512, window=128,
                              sink=True)),
    "ragged_prefill_mimo_cell_full_kind_in_place": (
        _ragged_prefill, dict(h=64, kvh=4, d=256, dv=128, bt=64, slots=64, pages=16384, table=512, layers=2)),
    "ragged_prefill_mimo_cell_window_kind_in_place": (
        _ragged_prefill, dict(h=64, kvh=8, d=256, dv=128, bt=64, slots=64, pages=1024, table=512, window=128,
                              sink=True, layers=5)),
    "ragged_prefill_mimo_cell_window_kind_64_rows": (
        _ragged_prefill, dict(h=64, kvh=8, d=256, dv=128, bt=64, cap=64, slots=64, pages=1024, table=512,
                              window=128, sink=True)),
    "moe_experts_decode_rows": (_moe_experts, dict(rows=64)),
    "moe_experts_prefill_rows": (_moe_experts, dict(rows=256)),
    # a state-space mixer at published widths (5120 channels x 16, 128 slots): a decode step's one row a slot, and
    # a pack's token blocks; and the attention layers beside it: one kv head, a query group of 20, 128-wide pages
    "ssm_scan_decode_step_128_slots": (_ssm_scan, dict(blocks=128, rows=1)),
    "ssm_scan_pack_256_rows": (_ssm_scan, dict(blocks=4, rows=64)),
    "ssm_scan_pack_64_rows": (_ssm_scan, dict(blocks=1, rows=64)),
    # the recurrence with heads and the two-matrix experts in a latent (ISSUE 44), at published shapes
    "ssd_scan_decode_step_96_slots": (_ssd_scan, dict(blocks=96, rows=1)),
    "ssd_scan_pack_256_rows": (_ssd_scan, dict(blocks=4, rows=64)),
    "moe_experts_relu2_decode_rows": (_moe_experts_relu2, dict(rows=1056)),
    "moe_experts_relu2_prefill_rows": (_moe_experts_relu2, dict(rows=2816)),
    # the delta rule's two forms, many narrow gated experts and attention at 2 kv heads of 256 (ISSUE 48), at the
    # qwen3-next cell's published shapes: 128 slots, runs of three DeltaNet layers and of one attention layer
    "gdn_scan_decode_step_128_slots": (_gdn_scan, dict(blocks=128, rows=1)),
    "gdn_scan_pack_256_rows": (_gdn_scan, dict(blocks=4, rows=64)),
    "gdn_scan_pack_of_8_row_blocks": (_gdn_scan, dict(blocks=4, rows=8)),  # (the rehearsal's packs, at published heads)
    "moe_experts_fine_decode_rows": (_moe_experts, dict(rows=320, layers=3, held=64, d=2048, m=512)),
    "moe_experts_fine_prefill_rows": (_moe_experts, dict(rows=640, layers=3, held=64, d=2048, m=512)),
    "paged_decode_2_kv_heads_of_256_in_place": (
        _paged_decode, dict(h=16, kvh=2, d=256, slots=128, pages=17408, table=1152, layers=1, write=True)),
    "ragged_prefill_2_kv_heads_of_256_in_place": (
        _ragged_prefill, dict(h=16, kvh=2, d=256, bt=64, cap=256, slots=128, pages=17408, table=1152, layers=1)),
    "ragged_prefill_2_kv_heads_of_256_64_rows_in_place": (
        _ragged_prefill, dict(h=16, kvh=2, d=256, bt=64, cap=64, slots=128, pages=17408, table=1152, layers=1)),
    "paged_decode_one_kv_head_group20_in_place": (
        _paged_decode, dict(h=20, kvh=1, slots=128, pages=16384, table=512, write=True)),
    "ragged_prefill_one_kv_head_group20": (
        _ragged_prefill, dict(h=20, kvh=1, bt=64, cap=256, slots=128, pages=16384, table=512)),
    "ragged_prefill_one_kv_head_group20_64_rows": (
        _ragged_prefill, dict(h=20, kvh=1, bt=64, cap=64, slots=128, pages=16384, table=512)),
    "ragged_prefill_one_kv_head_group20_in_place": (
        _ragged_prefill, dict(h=20, kvh=1, bt=64, cap=256, slots=128, pages=16384, table=512, layers=1)),
    # a closing window with pooled summaries at published widths (benchmarks/configs/evabyte-6.5b-serve-8l.json):
    # 32 kv heads and a query group of one over entry lists, 16 slots, a table of 1280; the pooling of a decode
    # step's filled pages in the carried stack (a pack pools by XLA's gather and scatter)
    "paged_decode_32_kv_heads_closing_cell_in_place": (
        _paged_decode, dict(slots=16, pages=1792, table=1280, layers=8, write=True)),
    "ragged_prefill_32_kv_heads_closing_cell": (
        _ragged_prefill, dict(bt=64, slots=16, pages=1792, table=1280)),
    "ragged_prefill_32_kv_heads_closing_cell_in_place": (
        _ragged_prefill, dict(bt=64, slots=16, pages=2112, table=1280, layers=8)),
    "eva_pool_decode_step_16_slots": (_eva_pool, dict(steps=16)),
    # dense-arena decode (single-stream generate(), the flat slot arena)
    "dense_decode_bf16": (_dense_decode, dict(bits=0)),
    "dense_decode_int8": (_dense_decode, dict(bits=8)),
}


# paged decode variants the chip's compiler refuses: the kernel copies whole
# pages out of the arena in HBM, and a slice of an HBM array whose last
# dimension is not a 128-multiple is not one Mosaic lays out: a 64-wide
# head, an int4 payload (head_dim / 2 wide), and the [.., page_size, 1]
# scale pages of every quantized arena. The gate keeps each off the kernel.
REFUSED = {
    **{
        f"paged_decode_{kv}_d{d}_sq{sq}": dict(h=h, kvh=h, d=d, sq=sq, bits=bits)
        for kv, bits in (("bf16", 0), ("int8", 8), ("int4", 4))
        for d, h in ((128, 32), (64, 12))
        for sq in (1, 5)
        if bits or d == 64
    },
    "paged_decode_int4_gqa_32q8kv": dict(kvh=8, bits=4),
    "paged_decode_int4_page128": dict(ps=128, bits=4),
    # keys 192 wide as they are: refused like 64; stored padded to 256 lanes
    # (paged_key_lanes) they are the compiled cases above
    "paged_decode_bf16_keys192_values128_full_kind": dict(h=64, kvh=4, d=192, dv=128),
    "paged_decode_bf16_keys192_values128_window_kind": dict(h=64, kvh=8, d=192, dv=128, window=128, sink=True),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_compiles_for_v5e(chip, case):
    build, kw = CASES[case]
    fn, args = build(chip, **kw)
    compiled = jax.jit(fn).lower(*args).compile()  # raises what the chip's compiler would
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("case", sorted(REFUSED))
def test_paged_decode_the_compiler_refuses_is_gated_off(chip, case, monkeypatch):
    """Both halves by name: Mosaic refuses the variant (when it stops, the
    gate can open), and the gate sends it to the dense path, so a server
    never meets the MosaicError at warmup."""
    kw = REFUSED[case]
    fn, args = _paged_decode(chip, **kw)
    with pytest.raises(Exception, match="must be aligned to tiling"):
        jax.jit(fn).lower(*args).compile()
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(A, "_decode_fallback_warned", set())
    gate = A._decode_kernel_gate(
        "paged", kw.get("sq", 1), kw.get("d", 128), kw.get("ps", 16), kw.get("bits", 0), paged=True,
        dv=kw.get("dv"))
    assert gate == (False, False)


# the names the benchmark's trace reduction finds the kernels by: an
# operation in the device trace carries its HLO instruction's name, which is
# the innermost scope of the call (the kernel's own name, or, for the paged
# decode kernel, which has none yet, the decoder's "attn" module). Under
# jax.grad the scope is wrapped by the transform, jvp(flash_attn_fwd), which
# XLA spells jvp_flash_attn_fwd_.
KERNEL_NAMES = {
    "flash_32k_context_fwd": {"flash_attn_fwd"},
    "flash_gqa_32q8kv_fwd_bwd": {"jvp_flash_attn_fwd_", "jvp_flash_attn_dq_", "jvp_flash_attn_dkv_"},
    "ragged_prefill_gqa_32q8kv_bf16": {"ragged_prefill_attn"},
    "ragged_prefill_serving_cell": {"ragged_prefill_attn"},
    "ragged_prefill_mimo_cell_window_kind": {"ragged_prefill_attn"},
    "paged_decode_bf16_d128_sq1": {"attn"},
    "paged_decode_serving_cell_in_place": {"attn"},
    "paged_decode_32_kv_heads_closing_cell_in_place": {"attn"},  # the gathered form, like the cell before it
    "moe_experts_decode_rows": {"moe_experts"},
    "latent_decode_in_place": {"mla_attn"},
    "latent_prefill_pack_256_rows_in_place": {"mla_prefill_attn"},
    "ssm_scan_decode_step_128_slots": {"ssm_scan"},
    "ssm_scan_pack_256_rows": {"ssm_scan"},
    "eva_pool_decode_step_16_slots": {"eva_pool"},
    "gdn_scan_decode_step_128_slots": {"gdn_scan"},
    "gdn_scan_pack_256_rows": {"gdn_scan"},
    "gdn_scan_pack_of_8_row_blocks": {"gdn_scan"},
    "moe_experts_fine_decode_rows": {"moe_experts"},
}


def _kernel_names(compiled_text: str) -> set:
    import re

    return {re.sub(r"(\.\d+)+$", "", m.group(1))
            for m in re.finditer(r"%(\S+) = [^\n]*custom_call_target=\"tpu_custom_call\"", compiled_text)}


@pytest.mark.parametrize("case", sorted(KERNEL_NAMES))
def test_kernels_carry_their_names_into_the_hlo(chip, case):
    build, kw = CASES[case]
    fn, args = build(chip, **kw)
    text = jax.jit(jax.named_scope("attn")(fn)).lower(*args).compile().as_text()
    assert _kernel_names(text) == KERNEL_NAMES[case]


# (kv heads, key lanes, value lanes, table entries, a window's pages, query heads) of each serving cell's cache kinds
# (benchmarks/configs/*.json), with the pages a block of the decode kernel's walk holds there, whether its softmax
# gathers every kv head's rows, and the pages a block of the prefill kernel's walk holds at the engine's token block
CELL_SHAPES = {
    "mistral_8x4": ((8, 128, None, 256, None, 32), 64, True, 32),
    "mimo_full_kind_4x16": ((4, 256, 128, 512, None, 64), 64, False, 32),
    "mimo_window_kind_8x8": ((8, 256, 128, 512, 9, 64), 16, False, 16),
    "jamba_1x20": ((1, 128, None, 512, None, 20), 64, False, 32),
    "evabyte_32x1": ((32, 128, None, 1280, None, 32), 16, True, 8),
    "gigachat_latent_1x64": ((1, 640, 0, 1600, None, 64), 64, False, None),
    "qwen3_next_2x8_of_256": ((2, 256, None, 1152, None, 16), 64, False, 32),
}


@pytest.mark.parametrize("cell", sorted(CELL_SHAPES))
def test_the_walks_blocks_and_the_softmaxs_form_at_every_cells_shape(cell):
    """What the kernels choose from the shapes alone, held by name: PR 43 changed the form of the decode kernel's
    softmax where a kv head's query rows are under a sublane tile and left every block as it was."""
    (kvh, lanes, value_lanes, table, window_pages, heads), block, gathered, prefill_block = CELL_SHAPES[cell]
    pdv = value_lanes or None
    assert A._paged_decode_block_pages(kvh, 16, lanes, jnp.bfloat16, 0, table, pdv=pdv,
                                       window_pages=window_pages) == block
    assert A._decode_rows_gathered(kvh, heads // kvh) is gathered
    if prefill_block is not None:  # the latent mode's pack kernel sizes its own block
        assert A._prefill_block_pages(kvh, 16, lanes, jnp.bfloat16, 0, table, 64 * (heads // kvh), pdv=pdv,
                                      window_pages=window_pages) == prefill_block


def _scoped_vmem(compiled_text: str) -> tuple:
    """``(the limits the program's one kernel call states, the bytes of VMEM it uses)``."""
    import json
    import re

    (call,) = re.findall(r"[^\n]*custom_call_target=\"tpu_custom_call\"[^\n]*", compiled_text)
    sizes = {key: [int(c["size"]) for c in json.loads(found)]
             for key, found in re.findall(r'"((?:used_)?scoped_memory_configs)":(\[[^\]]*\])', call)}
    (used,) = sizes["used_scoped_memory_configs"]
    return sizes["scoped_memory_configs"], used


@pytest.mark.parametrize("case", ["paged_decode_32_kv_heads_closing_cell_in_place", "paged_decode_serving_cell_in_place",
                                  "paged_decode_bf16_d128_sq5"])
def test_the_gathered_forms_vmem_request_is_under_its_limit(chip, case):
    """The gathered form adds a tile of scores and one of PV products to the page buffers (2.2 MB at 32 kv heads of
    five rows, the most): what the kernel asks of VMEM stays under the compiler's own limit of 16 MiB, and the call
    states none of its own."""
    build, kw = CASES[case]
    fn, args = build(chip, **kw)
    stated, used = _scoped_vmem(jax.jit(fn).lower(*args).compile().as_text())
    assert stated == []  # a call that stated a limit would carry it here
    assert 8 * 2**20 < used < 16 * 2**20


@pytest.mark.parametrize("case", ["gdn_scan_pack_256_rows", "gdn_scan_pack_of_8_row_blocks", "gdn_scan_decode_step_128_slots"])
def test_the_delta_rules_forms_are_one_kernel_under_its_vmem_limit(chip, case):
    """A pack's blocks run the chunked form (a chunk's products and its
    triangular system beside the slot's state in VMEM) and a decode step's the
    row walk: one ``pallas_call`` by the one name either way, and what it asks
    of VMEM is under the limit the call states (``_GDN_VMEM``)."""
    from accelerate_tpu.ops import ssm

    build, kw = CASES[case]
    fn, args = build(chip, **kw)
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert _kernel_names(text) == {"gdn_scan"}
    stated, used = _scoped_vmem(text)
    assert stated == [ssm._GDN_VMEM] and 4 * 2**20 < used < ssm._GDN_VMEM
    print(case, "scoped VMEM", used)


def _small_model(by_kind: bool, experts: bool = False):
    """Widths the paged kernel takes on the chip (128-multiple pages), small
    enough to build on the CPU: one kind, or a full and a window kind with
    keys 192 wide (stored padded to 256 lanes), values 128 and a sink.
    ``experts``: the window kind's layers (a run of two, and one alone) have
    four experts 384 wide, two a token."""
    from accelerate_tpu.models import DecoderConfig, DecoderLM

    common = dict(vocab_size=512, embed_dim=256, num_heads=8, mlp_dim=512, max_seq_len=512, dtype=jnp.bfloat16,
                  scan_layers=True, remat=False)
    if not by_kind:
        return DecoderLM(DecoderConfig(num_layers=3, num_kv_heads=2, head_dim=128, **common))
    return DecoderLM(DecoderConfig(
        num_layers=5, head_dim=192, v_head_dim=128, rope_dim=64, attn_value_scale=0.707,
        layer_kinds=(("full", dict(num_kv_heads=2)),
                     ("window", dict(num_kv_heads=4, attn_window=32, attn_sink=True, rope_theta=1e4,
                                     **(dict(mlp_dim=384, moe_num_experts=4, moe_top_k=2) if experts else {})))),
        layer_pattern=(0, 1, 1, 0, 1), **common))


def _small_eva_model():
    """A closing window with pooled summaries (ops/eva.py) at a page the chip
    takes: chunks and pages of 16, a window of 256, heads of 128."""
    from accelerate_tpu.models import DecoderConfig, DecoderLM

    return DecoderLM(DecoderConfig(
        vocab_size=512, embed_dim=256, num_heads=2, num_kv_heads=2, head_dim=128, mlp_dim=512, max_seq_len=1024,
        dtype=jnp.bfloat16, scan_layers=True, remat=False, num_layers=3, eva_window=256, eva_chunk=16))


def _compile_serving_program(eng, chip, program: str):
    """The engine's decode step or its packed prefill at 256 rows, compiled
    for the described chip with XLA's optimizations on (the suite compiles
    with most of them off; these tests are about what they leave)."""
    S = lambda tree: jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=chip), tree)
    if program == "decode_step":
        fn = jax.jit(eng._step_core, donate_argnums=(1, 2, 3, 5))
        args = (eng.params, eng._arena, eng._tokens, eng._lengths, eng._active, eng._rngs, eng._tables_arg())
    else:
        fn = jax.jit(eng._ragged_prefill_fn(256).__wrapped__, donate_argnums=(1,))
        args = eng._ragged_warm_args(256)
    unoptimized = jax.config.values["jax_disable_most_optimizations"]
    jax.config.update("jax_disable_most_optimizations", False)
    try:
        return fn.lower(*S(args)).compile()
    finally:
        jax.config.update("jax_disable_most_optimizations", unoptimized)


@pytest.mark.parametrize("threading", ["in_place", "split_by_layer"])
@pytest.mark.parametrize("shape", ["one_kind", "by_kind", "eva"])
@pytest.mark.parametrize("program", ["decode_step", "packed_prefill"])
def test_the_decode_step_holds_one_arena(chip, monkeypatch, program, shape, threading):
    """A whole serving program of a small paged engine, compiled for the
    chip: the decode step, and the packed prefill at 256 rows. With the arena
    carried through the layer scan and written by the program's kernel, no
    operation of the program has the stacked arena's or a layer's pages'
    shape as its result but the kernels themselves (no slice out of the
    stack, no copy to the scatter's layout and back, no update-slice, no
    scatter), and its temporaries are less than one layer's pages.
    ``split_by_layer`` is the control, the threading every other call keeps:
    the same check finds them all there. ``eva``: a closing window, whose
    filled pages the ``eva_pool`` kernel pools in the same carried stack."""
    import re

    import accelerate_tpu.models.decoder as decoder
    from accelerate_tpu.parallel.sharding import unbox_params
    from accelerate_tpu.serving import ServingEngine

    model = _small_eva_model() if shape == "eva" else _small_model(shape == "by_kind")
    params, _ = unbox_params(model.init_variables(jax.random.PRNGKey(0), batch_size=1, seq_len=16)["params"])
    params = jax.tree_util.tree_map(lambda x: x.astype(jnp.bfloat16), params)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    if threading == "split_by_layer":
        monkeypatch.setattr(decoder, "arena_in_place", lambda *a, **k: False)
    sizes = {"one_kind": dict(max_cache_len=512, num_pages=1025),
             "by_kind": dict(max_cache_len=512, num_pages=1025, kind_pages={"window32": 513}),
             "eva": dict(max_cache_len=1024, num_pages=1025)}[shape]
    eng = ServingEngine(model, params, num_slots=8, page_size=16, prefix_cache=False, **sizes)
    assert eng.metrics()["serving/decode_kernel_active"] and eng.metrics()["serving/prefill_kernel_active"]
    compiled = _compile_serving_program(eng, chip, program)
    text = compiled.as_text()
    paged = [x for x in jax.tree_util.tree_leaves(eng._arena) if x.ndim == 5]
    shapes = {",".join(map(str, shp)) for x in paged for shp in (x.shape, x.shape[1:])}
    moved = re.compile(r"= \w+\[(%s)\]\S* (copy|copy-start|dynamic-slice|dynamic-update-slice|scatter)\("
                       % "|".join(shapes))
    found = sorted({m.group(2) for m in moved.finditer(text)})
    one_layer = min(x.nbytes // x.shape[0] for x in paged)
    temp = compiled.memory_analysis().temp_size_in_bytes
    # what decode_attn_roofline_pct and prefill's readers find the kernels by
    kernels = {"decode_step": {"attn"}, "packed_prefill": {"ragged_prefill_attn"}}[program]
    if shape == "eva" and threading == "in_place":  # (split by layer, XLA's gather and scatter pool)
        kernels = kernels | {"eva_pool"}
    assert kernels <= _kernel_names(text)
    gauge = {"decode_step": "serving/arena_in_place", "packed_prefill": "serving/prefill_arena_in_place"}[program]
    assert eng.metrics()[gauge] == 1  # (the engine's own view is not patched)
    if threading == "in_place":
        assert not found and temp < one_layer, (found, temp, one_layer)
    else:
        # (its temporaries say nothing at this size: the compiler keeps them in VMEM, 128 MiB on a v5e;
        # at the serving cell's size they are 4.16 GiB against 0.3 MiB: PERF.md, PR 29)
        assert {"copy", "dynamic-update-slice"} <= set(found), found


@pytest.mark.parametrize("experts", ["from_stack", "sliced"])
@pytest.mark.parametrize("program", ["decode_step", "packed_prefill"])
def test_no_layers_experts_are_copied_out_of_their_stack(chip, monkeypatch, program, experts):
    """Both serving programs of a small by-kind model whose window layers have
    experts, a run of two among them, compiled for the chip: the
    ``moe_experts`` kernel is in each, and with the run's stacked expert
    leaves riding the layer scan (``models/decoder.expert_stacks``) no
    operation of the program has an expert leaf's or its stack's shape as its
    result: no slice out of the stack, no copy. ``sliced`` is the control,
    the stack withheld: every layer reads its own slice (``w[None]`` to the
    kernel, so it keeps a leading 1), and the same search finds the
    ``dynamic-slice``. (At this size the compiler also prefetches whole
    leaves into VMEM, ``copy-start`` and ``slice-start`` with a tuple for a
    result, either way; nothing of the cell's 268 MB a leaf fits there, and
    this search does not read them.)"""
    import re

    import accelerate_tpu.models.decoder as decoder
    from accelerate_tpu.parallel.sharding import unbox_params
    from accelerate_tpu.serving import ServingEngine

    model = _small_model(True, experts=True)
    params, _ = unbox_params(model.init_variables(jax.random.PRNGKey(0), batch_size=1, seq_len=16)["params"])
    params = jax.tree_util.tree_map(lambda x: x.astype(jnp.bfloat16), params)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    eng = ServingEngine(model, params, num_slots=8, page_size=16, prefix_cache=False, max_cache_len=512,
                        num_pages=1025, kind_pages={"window32": 513})
    assert eng.metrics()["serving/experts_from_stack"] == 1
    if experts == "sliced":  # (after the engine took its own view)
        monkeypatch.setattr(decoder, "expert_stacks", lambda *a, **k: {})
    text = _compile_serving_program(eng, chip, program).as_text()
    assert "moe_experts" in _kernel_names(text)
    run = eng.params["layers_1"]["block"]["moe_mlp"]  # the run of two
    leaves = [run[k] for k in ("w_gate", "w_up", "w_down")]
    assert [x.shape for x in leaves] == [(2, 4, 256, 384), (2, 4, 256, 384), (2, 4, 384, 256)]
    shapes = {",".join(map(str, shp)) for x in leaves for shp in (x.shape, x.shape[1:], (1,) + x.shape[1:])}
    moved = re.compile(r"= \w+\[(%s)\]\S* (copy|copy-start|dynamic-slice)\(" % "|".join(shapes))
    found = sorted({m.group(2) for m in moved.finditer(text)})
    if experts == "from_stack":
        assert not found, found
    else:
        assert "dynamic-slice" in found, found


@pytest.mark.parametrize("program", ["decode_step", "packed_prefill"])
def test_both_serving_programs_hold_one_copy_of_the_state(chip, monkeypatch, program):
    """A small model with state-space layers beside an attention layer without
    rotation (one kv head, a query group of 4), both serving programs
    compiled for the chip: the ``ssm_scan`` kernel is in each, and no
    operation of the program has the stacked states' or one layer's states'
    shape as its result but the kernel: no slice out of the stack, no copy,
    no update-slice. The convolution's kept inputs, a 16th of the state, move
    through XLA and are not held to that."""
    import re

    from accelerate_tpu.models import DecoderConfig, DecoderLM
    from accelerate_tpu.parallel.sharding import unbox_params
    from accelerate_tpu.serving import ServingEngine

    model = DecoderLM(DecoderConfig(
        vocab_size=512, embed_dim=256, num_heads=4, num_kv_heads=1, head_dim=128, rope_dim=0, mlp_dim=512,
        max_seq_len=512, dtype=jnp.bfloat16, residual_dtype=jnp.float32, scan_layers=True, remat=False,
        num_layers=5, layer_kinds=(("state_space", dict(mixer="ssm", ssm_state_dim=16, ssm_dt_rank=16)),
                                   ("attention", dict(mixer="attention"))), layer_pattern=(0, 0, 1, 0, 0)))
    params, _ = unbox_params(model.init_variables(jax.random.PRNGKey(0), batch_size=1, seq_len=16)["params"])
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    eng = ServingEngine(model, params, num_slots=8, max_cache_len=512, page_size=16, num_pages=257, prefix_cache=False)
    m = eng.metrics()
    assert m["serving/ssm_kernel_active"] == 1 and m["serving/state_in_place"] == 1 and m["serving/decode_kernel_active"]
    S = lambda tree: jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=chip), tree)
    if program == "decode_step":
        fn = jax.jit(eng._step_core, donate_argnums=(1, 2, 3, 5))
        args = (eng.params, eng._arena, eng._tokens, eng._lengths, eng._active, eng._rngs, eng._tables_arg())
    else:
        fn, args = eng._ragged_prefill_fn(256), eng._ragged_warm_args(256)
    unoptimized = jax.config.values["jax_disable_most_optimizations"]
    jax.config.update("jax_disable_most_optimizations", False)
    try:
        text = fn.lower(*S(args)).compile().as_text()
    finally:
        jax.config.update("jax_disable_most_optimizations", unoptimized)
    assert "ssm_scan" in _kernel_names(text)
    states = [x for p, x in jax.tree_util.tree_flatten_with_path(eng._arena)[0] if p[-1].key == "ssm_state"]
    assert len(states) == 2 and all(x.shape == (2, 8, 16, 512) for x in states)
    shapes = {",".join(map(str, shp)) for x in states for shp in (x.shape, x.shape[1:])}
    moved = re.compile(r"= \w+\[(%s)\]\S* (copy|copy-start|dynamic-slice|dynamic-update-slice|scatter)\("
                       % "|".join(shapes))
    assert not sorted({m.group(2) for m in moved.finditer(text)})


@pytest.mark.parametrize("outer_manual", [(), ("fsdp", "tensor"), ("fsdp",)],
                         ids=["jit", "inside_all_manual", "inside_partly_manual"])
def test_flash_under_a_four_chip_mesh_compiles(v5e, monkeypatch, outer_manual):
    """The sharded trainer's attention: under an fsdp2 x tp2 mesh the kernel
    must sit inside a shard_map. Bare, the SPMD partitioner refuses it
    ("Mosaic kernels cannot be automatically partitioned") — which no
    CPU-sim dry run could show. The compressed-replica train step and
    LocalSGD already run the model inside a shard_map of their own: there
    the wrapper maps only the axes that are still automatic."""
    import numpy as np
    from jax import shard_map
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from accelerate_tpu.parallel.context import dot_product_attention_sharded

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    mesh = Mesh(np.array(v5e).reshape(2, 2), ("fsdp", "tensor"))
    spec = NamedSharding(mesh, P("fsdp", "tensor", None, None))
    x = jax.ShapeDtypeStruct((4, 32, 2048, 128), jnp.bfloat16, sharding=spec)

    def step(attend):
        if outer_manual:
            outer = P(*(a if a in outer_manual else None for a in ("fsdp", "tensor")))
            attend = shard_map(attend, mesh=mesh, in_specs=(outer,) * 3, out_specs=outer,
                               axis_names=set(outer_manual), check_vma=False)
        loss = lambda q, k, v: attend(q, k, v).astype(jnp.float32).sum()
        return jax.jit(jax.grad(loss, argnums=(0, 1, 2)))

    wrapped = lambda q, k, v: dot_product_attention_sharded(q, k, v, mesh, causal=True)
    assert "tpu_custom_call" in step(wrapped).lower(x, x, x).compile().as_text()
    if len(outer_manual) < 2:  # an automatic axis is left: the bare kernel is refused
        bare = lambda q, k, v: A.dot_product_attention(q, k, v, causal=True)
        with pytest.raises(NotImplementedError, match="automatically partitioned"):
            step(bare).lower(x, x, x)


def test_a_tensor_parallel_block_exchanges_rows_instead_of_all_reducing(v5e, monkeypatch):
    """The training cell's block (hidden 4096, MLP 14336, 32 heads over 8 kv
    heads, rows [8, 4096], remat'd and scanned) forward and backward under
    fsdp2 x tp2: its four tensor-parallel products take the exchange path
    (parallel/context.gather_einsum, einsum_scatter), so the compiled program
    holds no all-reduce of the residual's shape, and the hops are
    asynchronous (a start and a done the scheduler puts products between)."""
    import re

    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from accelerate_tpu.models import DecoderConfig
    from accelerate_tpu.models.decoder import StageStack, _rotary_tables
    from accelerate_tpu.parallel.context import record_exchanged_products
    from accelerate_tpu.parallel.sharding import infer_param_sharding, unbox_params
    from accelerate_tpu.utils.dataclasses import ShardingConfig

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    mesh = Mesh(np.array(v5e).reshape(2, 2), ("fsdp", "tensor"))
    cfg = DecoderConfig(vocab_size=512, num_layers=1, embed_dim=4096, num_heads=32, num_kv_heads=8, head_dim=128,
                        mlp_dim=14336, max_seq_len=4096, dtype=jnp.bfloat16, scan_layers=True, remat=True,
                        remat_policy="save_attention")
    stack = StageStack(cfg, mesh)
    sin, cos = _rotary_tables(jnp.arange(4096), cfg, cfg.dtype)
    rows = jnp.zeros((8, 4096, 4096), cfg.dtype)
    raw, axes = unbox_params(jax.eval_shape(lambda: stack.init(jax.random.PRNGKey(0), rows, sin, cos))["params"])
    shardings = infer_param_sharding(raw, mesh, ShardingConfig(fsdp=2, tensor_parallel=2), axes)
    params = jax.tree_util.tree_map(lambda p, s: jax.ShapeDtypeStruct(p.shape, cfg.dtype, sharding=s), raw, shardings)
    x = jax.ShapeDtypeStruct(rows.shape, cfg.dtype, sharding=NamedSharding(mesh, P("fsdp", "tensor")))
    loss = lambda p, x: stack.apply({"params": p}, x, sin, cos).astype(jnp.float32).sum()
    unoptimized = jax.config.values["jax_disable_most_optimizations"]
    jax.config.update("jax_disable_most_optimizations", False)
    try:
        with record_exchanged_products() as exchanged:
            text = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(params, x).compile().as_text()
    finally:
        jax.config.update("jax_disable_most_optimizations", unoptimized)
    assert len(exchanged) == 4
    assert "collective-permute-start" in text
    assert not re.findall(r"= bf16\[4,4096,4096\]\S* all-reduce(?:-start)?\(", text)


def test_gates_admit_only_what_compiles(monkeypatch):
    """Every (head_dim, page, KV storage) the shape gates admit on the chip
    is among the compiled cases above, and what they refuse they refuse by
    name: an int4 head_dim of 64 packs to a 32-wide payload the gate keeps
    off the kernel (warn-once + dense path), never a MosaicError at warmup."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(A, "_decode_fallback_warned", set())  # keep the warn-once state of other tests
    for d in (128, 64):
        for bits in (0, 8, 4):
            admitted = not (bits == 4 and d == 64)
            assert A._decode_kernel_gate("paged", 1, d, 16, bits) == (admitted, False)
            assert A._prefill_kernel_gate("ragged", d, 16, 8, bits) == (admitted, False)
            # the page-table decode kernel: CASES has what it admits, REFUSED the rest
            paged = A._decode_kernel_gate("paged", 1, d, 16, bits, paged=True)
            assert paged == (d == 128 and not bits, False)
    # keys 192 wide: the decode kernel refuses them as they are and takes
    # them in the layout a model gives its pages (256 lanes, values 128);
    # the prefill kernel takes both
    assert A._decode_kernel_gate("paged", 1, 192, 16, 0, paged=True, dv=128) == (False, False)
    assert A._decode_kernel_gate("paged", 1, A.paged_key_lanes(192), 16, 0, paged=True, dv=128) == (True, False)
    assert A._prefill_kernel_gate("ragged", 192, 16, 64, dv=128) == (True, False)
    assert A._prefill_kernel_gate("ragged", A.paged_key_lanes(192), 16, 64, dv=128) == (True, False)
    from accelerate_tpu.models import DecoderConfig

    kind = DecoderConfig(num_heads=64, num_kv_heads=8, head_dim=192, v_head_dim=128, embed_dim=4096,
                         kv_page_size=16, kv_num_pages=1024, attn_window=128, attn_sink=True)
    assert A.decode_kernel_active(kind) and A.prefill_kernel_active(kind)
    narrow = DecoderConfig(num_heads=12, head_dim=64, kv_page_size=16, kv_num_pages=64)
    assert not A.decode_kernel_active(narrow) and A.prefill_kernel_active(narrow)
    for kv in ("int8", "int4"):
        quantized = DecoderConfig(num_heads=32, num_kv_heads=8, head_dim=128, kv_page_size=16, kv_num_pages=64,
                                  kv_cache_dtype=kv)
        assert not A.decode_kernel_active(quantized) and A.prefill_kernel_active(quantized)


@pytest.mark.parametrize("program", ["decode_step", "packed_prefill"])
def test_the_latent_serving_programs_compile_at_the_published_widths(chip, monkeypatch, program):
    """The gigachat3 cell's engine as its configuration file sizes it (six
    layers at published widths, 8 of 256 experts, 16 slots of 25,600; built
    from shapes alone, nothing is allocated), both programs compiled for the
    described chip: Mosaic takes the 640-lane slices of the latent pages, the
    program's kernels are the latent ones and ``moe_experts``, the arena is
    aliased to the program's output and no operation copies, slices or
    scatters it, and the program's temporaries are a few MB. What the backend's
    ``memory_peak_bytes`` cannot see (PERF.md section 7) is here:
    ``compiled.memory_analysis()`` of both programs."""
    import json
    import os
    import re
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    for p in (root, os.path.join(root, "benchmarks")):
        if p not in sys.path:
            sys.path.insert(0, p)
    import manifest
    import weights
    from accelerate_tpu.serving import ServingEngine

    with open(os.path.join(root, "benchmarks", "configs", "gigachat3.1-702b-serve-6l-ep32.json")) as f:
        c = json.load(f)
    arch, s = manifest.load_arch(c["model_type"]), c["serving"]
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = arch.decoder_config(c, max_seq_len=s["max_cache_len"], remat=False)
    params = jax.eval_shape(
        lambda k: arch.to_program_tree(c)(weights.make(arch.reference, c, k, jnp.bfloat16)), weights.seed_key(1))
    eng = ServingEngine(arch.module(cfg), params, page_size=s["page_size"], num_slots=s["num_slots"],
                        max_cache_len=s["max_cache_len"], num_pages=s["num_pages"], **s["engine_kwargs"])
    m = eng.metrics()
    assert m["serving/mla_kernel_active"] == 1 and m["serving/latent_bytes_per_token"] == 1280
    assert m["serving/arena_in_place"] == m["serving/prefill_arena_in_place"] == m["serving/experts_from_stack"] == 1
    compiled = _compile_serving_program(eng, chip, program)
    text, mem = compiled.as_text(), compiled.memory_analysis()
    assert _kernel_names(text) == {"moe_experts", {"decode_step": "mla_attn", "packed_prefill": "mla_prefill_attn"}[program]}
    paged = [x for x in jax.tree_util.tree_leaves(eng._arena) if x.ndim == 5]
    assert sorted(x.shape for x in paged) == [(1, s["num_pages"], 1, 16, 640), (5, s["num_pages"], 1, 16, 640)]
    shapes = {",".join(map(str, shp)) for x in paged for shp in (x.shape, x.shape[1:])}
    moved = re.compile(r"= \w+\[(%s)\]\S* (copy|copy-start|dynamic-slice|dynamic-update-slice|scatter)\("
                       % "|".join(shapes))
    assert not moved.search(text)
    arena = sum(x.nbytes for x in paged)
    weights_bytes = 2 * arch.total_params(c)
    assert arena == s["num_pages"] * 16 * 1280 * 6 and mem.alias_size_in_bytes >= arena
    assert mem.temp_size_in_bytes < 64 * 2**20, mem.temp_size_in_bytes
    assert weights_bytes + arena <= mem.argument_size_in_bytes < weights_bytes + arena + 64 * 2**20
    print(program, "arguments", mem.argument_size_in_bytes, "temporaries", mem.temp_size_in_bytes,
          "aliased", mem.alias_size_in_bytes)


@pytest.mark.parametrize("program", ["decode_step", "packed_prefill"])
def test_the_half_block_serving_programs_compile_at_the_published_widths(chip, monkeypatch, program):
    """The nemotron3 cell's engine as its configuration file sizes it (11
    published layers as 6 blocks in 4 scans, 128 of 512 experts, 96 slots of
    9,216; built from shapes alone, nothing is allocated), both programs
    compiled for the described chip: the program's kernels are ``ssd_scan``,
    ``moe_experts_relu2`` and the attention kernel; the arena (2.07 GB of state,
    0.27 GB of pages) is aliased to the program's output, and no operation
    copies, slices or scatters a layer's states or the stack of them; the
    arguments are the weights and the arena (11.6 GB), the temporaries under
    128 MB."""
    import json
    import os
    import re
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    for p in (root, os.path.join(root, "benchmarks")):
        if p not in sys.path:
            sys.path.insert(0, p)
    import manifest
    import weights
    from accelerate_tpu.serving import ServingEngine, pages

    with open(os.path.join(root, "benchmarks", "configs", "nemotron3-super-120b-serve-11l-ep4.json")) as f:
        c = json.load(f)
    arch, s = manifest.load_arch(c["model_type"]), c["serving"]
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = arch.decoder_config(c, max_seq_len=s["max_cache_len"], remat=False)
    params = jax.eval_shape(
        lambda k: arch.to_program_tree(c)(weights.make(arch.reference, c, k, jnp.bfloat16)), weights.seed_key(1))
    eng = ServingEngine(arch.module(cfg), params, page_size=s["page_size"], num_slots=s["num_slots"],
                        max_cache_len=s["max_cache_len"], num_pages=s["num_pages"], **s["engine_kwargs"])
    m = eng.metrics()
    assert m["serving/ssd_kernel_active"] == 1 and m["serving/state_bytes_per_slot"] == arch.slot_state_bytes(c) == 21_585_920
    assert m["serving/arena_in_place"] == m["serving/prefill_arena_in_place"] == 1
    assert m["serving/state_in_place"] == m["serving/experts_from_stack"] == 1
    compiled = _compile_serving_program(eng, chip, program)
    text, mem = compiled.as_text(), compiled.memory_analysis()
    assert _kernel_names(text) == {"ssd_scan", "moe_experts_relu2",
                                   {"decode_step": "attn", "packed_prefill": "ragged_prefill_attn"}[program]}
    states = [x for p, x in jax.tree_util.tree_flatten_with_path(eng._arena)[0] if p[-1].key == "ssm_state"]
    assert sorted(x.shape for x in states) == [(1, 96, 64, 128, 128)] * 2 + [(3, 96, 64, 128, 128)]
    shapes = {",".join(map(str, shp)) for x in states for shp in (x.shape, x.shape[1:])}
    moved = re.compile(r"= \w+\[(%s)\]\S* (copy|copy-start|dynamic-slice|dynamic-update-slice|scatter)\("
                       % "|".join(shapes))
    assert not moved.search(text)
    arena, weights_bytes = pages.arena_nbytes(eng._arena), 2 * arch.total_params(c)
    assert pages.state_nbytes(eng._arena) == 96 * 21_585_920 and mem.alias_size_in_bytes >= arena
    assert mem.temp_size_in_bytes < 128 * 2**20, mem.temp_size_in_bytes
    assert weights_bytes + arena <= mem.argument_size_in_bytes < weights_bytes + arena + 64 * 2**20
    print(program, "arguments", mem.argument_size_in_bytes, "temporaries", mem.temp_size_in_bytes,
          "aliased", mem.alias_size_in_bytes)


@pytest.mark.parametrize("program", ["decode_step", "packed_prefill"])
def test_the_delta_rule_serving_programs_compile_at_the_published_widths(chip, monkeypatch, program):
    """The qwen3-next cell's engine as its configuration file sizes it (12
    published layers ``LLLF`` three times as 6 scans, 64 of 512 experts, 128
    slots of 18,432; built from shapes alone, nothing is allocated), both
    programs compiled for the described chip: the program's kernels are
    ``gdn_scan``, ``moe_experts`` and the attention kernel; the arena (2.53 GB
    of state, 1.71 GB of pages) is aliased to the program's output, and no
    operation copies, slices or scatters a layer's states or the stack of
    them; the arguments are the weights and the arena (10.1 GB), the
    temporaries under 256 MB."""
    import json
    import os
    import re
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    for p in (root, os.path.join(root, "benchmarks")):
        if p not in sys.path:
            sys.path.insert(0, p)
    import manifest
    import weights
    from accelerate_tpu.serving import ServingEngine, pages

    with open(os.path.join(root, "benchmarks", "configs", "qwen3-next-80b-serve-12l-ep8.json")) as f:
        c = json.load(f)
    arch, s = manifest.load_arch(c["model_type"]), c["serving"]
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = arch.decoder_config(c, max_seq_len=s["max_cache_len"], remat=False)
    assert cfg.num_params == arch.total_params(c) == 2_929_374_400
    params = jax.eval_shape(
        lambda k: arch.to_program_tree(c)(weights.make(arch.reference, c, k, jnp.bfloat16)), weights.seed_key(1))
    eng = ServingEngine(arch.module(cfg), params, page_size=s["page_size"], num_slots=s["num_slots"],
                        max_cache_len=s["max_cache_len"], num_pages=s["num_pages"], **s["engine_kwargs"])
    m = eng.metrics()
    assert m["serving/gdn_kernel_active"] == 1
    assert m["serving/state_bytes_per_slot"] == arch.slot_state_bytes(c) == 9 * (2_097_152 + 3 * 8_192 * 4)
    assert m["serving/arena_in_place"] == m["serving/prefill_arena_in_place"] == 1
    assert m["serving/state_in_place"] == m["serving/experts_from_stack"] == 1
    compiled = _compile_serving_program(eng, chip, program)
    text, mem = compiled.as_text(), compiled.memory_analysis()
    assert _kernel_names(text) == {"gdn_scan", "moe_experts",
                                   {"decode_step": "attn", "packed_prefill": "ragged_prefill_attn"}[program]}
    states = [x for p, x in jax.tree_util.tree_flatten_with_path(eng._arena)[0] if p[-1].key == "ssm_state"]
    assert sorted(x.shape for x in states) == [(3, 128, 32, 128, 128)] * 3
    shapes = {",".join(map(str, shp)) for x in states for shp in (x.shape, x.shape[1:])}
    moved = re.compile(r"= \w+\[(%s)\]\S* (copy|copy-start|dynamic-slice|dynamic-update-slice|scatter)\("
                       % "|".join(shapes))
    assert not moved.search(text)
    arena, weights_bytes = pages.arena_nbytes(eng._arena), 2 * arch.total_params(c)
    assert pages.state_nbytes(eng._arena) == 128 * arch.slot_state_bytes(c) and mem.alias_size_in_bytes >= arena
    assert mem.temp_size_in_bytes < 256 * 2**20, mem.temp_size_in_bytes
    assert weights_bytes + arena <= mem.argument_size_in_bytes < weights_bytes + arena + 64 * 2**20
    print(program, "arguments", mem.argument_size_in_bytes, "temporaries", mem.temp_size_in_bytes,
          "aliased", mem.alias_size_in_bytes)

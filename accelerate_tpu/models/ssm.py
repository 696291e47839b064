"""The state-space mixers a layer kind may state in attention's place:
:class:`SelectiveSSM` (``DecoderConfig.mixer == "ssm"``: Mamba-1, with the
inner norms of the Jamba family), :class:`Mamba2Mixer` (``"ssd"``: heads,
further down) and :class:`GatedDeltaNet` (``"gdn"``: a state the delta rule
corrects, last). Mamba-1:

    [u, z] = h W_in                               (E -> 2 x D, D = ssm_expand x E)
    u'     = silu(conv(u) + b_conv)               causal, depthwise, ssm_conv_width taps
    [d, B, C] = u' W_x                            (D -> R + N + N)
    d, B, C each through an RMSNorm of its own    (ssm_inner_norms)
    dt     = softplus(d W_dt + b_dt)              (R -> D)
    S_t    = exp(dt_t (x) A) * S_{t-1} + (dt_t * u'_t) (x) B_t,   A = -exp(A_log)
    y_t    = S_t^T C_t + D_skip * u'_t
    out    = (y * silu(z)) W_out                  (D -> E)

The recurrence is float32 (``ops/ssm.py``); the projections take the
config's dtype like every other matrix multiplication of the model.

Three call forms, one mathematics:

- **a whole sequence** a batch row, from a zero state or, with ``use_cache``
  in a decode call, from the state the cache holds (the plain forward pass
  and ``generate()``): the ``jax.numpy`` recurrence, differentiable.
- **a packed ragged prefill** (``ragged_slots``): row r of the one batch row
  is position ``cache_positions[0, r]`` of slot ``ragged_slots[r]``, in token
  blocks of ``prefill_kernel_block`` rows, each of one slot. A slot whose
  first packed position is 0 starts from a zero state inside the program (a
  request's first chunk; the reset is no dispatch); a later chunk resumes
  from the slot's state. Padding rows (position -1) advance nothing.
- **a decode step** (``cache_positions`` alone): one row a slot;
  ``kv_lengths`` says which slots are live (0: the slot's state and
  convolution inputs stay as they are).

What a slot keeps, in the "cache" collection beside the paged leaves:
``ssm_state`` [slots, N, D] float32 (the wide dimension on lanes) and
``conv_state`` [slots, ssm_conv_width - 1, D], the convolution's last raw
inputs (``serving/pages.STATE_LEAF_NAMES`` finds them by these names; with
heads the state is [slots, D / lane, N, lane], ``ops/ssm.ssd_state_shape``,
and the convolution's channels are D + 2 G N, kept in float32; Gated DeltaNet
keeps [slots, value heads, dk, dv] and the channels of q, k and v). Where the layer scan carries the collection whole (``cache_layer``),
both are the layers' stacks with a leading layer axis and are updated in
place: the kernel takes the stack and the layer, and the convolution's
inputs are one dynamic slice in and one out.
"""

from __future__ import annotations

from typing import Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from ..ops.layers import rms_norm, two_term_matmul
from ..ops.ssm import gdn_scan, ssd_scan, ssd_state_shape, ssm_scan
from .configs import DecoderConfig

def _inverse_softplus_log_uniform(lo: float = 1e-3, hi: float = 1e-1):
    """The step's bias as the family initialises it: the inverse softplus of
    a step drawn log-uniform in [lo, hi]."""
    def init(key, shape, dtype=jnp.float32):
        dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32) * (jnp.log(hi) - jnp.log(lo)) + jnp.log(lo))
        return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)
    return init


def _a_log_init(key, shape, dtype=jnp.float32):
    """A = -(1..N) a channel: ``a_log`` [N, D] = log(n + 1)."""
    del key
    n, d = shape
    return jnp.broadcast_to(jnp.log(jnp.arange(1, n + 1, dtype=jnp.float32))[:, None], (n, d)).astype(dtype)


def _blocks_and_halo(cfg, u, conv, read, serving: bool, resume: bool,
                     cache_positions, ragged_slots, slot_hist, kv_lengths):
    """The blocks the recurrence walks and the convolution's inputs before
    each, for the three call forms (the module's docstring), of either
    mixer: ``u`` [b, s, channels] are the convolution's raw inputs. Returns
    ``(blocks, rows a block, slot [blocks], live rows [blocks], fresh
    [blocks], u as blocks, halo [blocks, k - 1, channels])``."""
    k, dt_ = cfg.ssm_conv_width, u.dtype
    b, s, d = u.shape
    if serving and ragged_slots is not None:
        # a pack: token blocks of one slot each; a block's first rows take
        # the block before it where that continues its slot's segment, else
        # the slot's kept inputs (none before position 0)
        bt = cfg.prefill_kernel_block
        if not bt or s % bt or b != 1:
            raise ValueError(
                f"a packed prefill is one batch row of whole token blocks "
                f"(prefill_kernel_block={bt}), got {u.shape[:2]}")
        nb = s // bt
        row_pos = jnp.reshape(cache_positions, (nb, bt))
        first = row_pos[:, 0]
        rows = jnp.sum(row_pos >= 0, axis=1).astype(jnp.int32)
        slot = jnp.where(rows > 0, ragged_slots[::bt], -1)
        at = jnp.maximum(slot, 0)
        fresh = (first == 0).astype(jnp.int32)
        blocks = u.reshape(nb, bt, d)
        kept = read(conv)[at]                                           # [nb, k-1, d]
        back = first[:, None] - jnp.arange(k - 1, 0, -1)[None, :]       # the positions before the block
        kept = jnp.where((back >= 0)[..., None], kept, 0)
        before = jnp.concatenate([jnp.zeros((1, k - 1, d), dt_), blocks[:-1, bt - (k - 1):]], axis=0)
        continues = (first > slot_hist[at]) & (slot >= 0)
        halo = jnp.where(continues[:, None, None], before, kept)
    else:
        # a sequence a batch row (or a decode step's one row a slot)
        nb, bt = b, s
        slot = jnp.arange(b, dtype=jnp.int32)
        live = jnp.ones((b,), bool) if not serving or kv_lengths is None else kv_lengths > 0
        rows = jnp.where(live, s, 0).astype(jnp.int32)
        fresh = jnp.full((b,), 0 if resume else 1, jnp.int32)
        blocks = u
        halo = read(conv) if resume else jnp.zeros((b, k - 1, d), dt_)
    return nb, bt, slot, rows, fresh, blocks, halo


def _stack_and_layer(state, cache_layer, zero_shape):
    """The stack of states the recurrence advances and this layer's index in
    it: the scanned stack carried whole (``cache_layer``), this layer's own
    leaf as a stack of one, or zeros a block where nothing is kept."""
    if state is None:
        return jnp.zeros((1, *zero_shape), jnp.float32), 0
    return (state.value, cache_layer) if cache_layer is not None else (state.value[None], 0)


def _keep_conv_tail(conv, read, ext, rows, slot, packed: bool, cache_layer):
    """Write the last ``k - 1`` raw inputs of each block that ends its slot's
    rows here into the slot's ``conv_state`` (``ext``: halo and block)."""
    k1 = conv.value.shape[-2]
    tail = jax.vmap(lambda e_, n_: jax.lax.dynamic_slice_in_dim(e_, n_, k1, axis=0))(ext, rows)
    tail = tail.astype(conv.value.dtype)
    if packed:
        # (the next block is another slot's or has no rows); the others
        # write past the last slot and are dropped
        ends = (slot >= 0) & (jnp.concatenate([slot[1:], jnp.full((1,), -1, slot.dtype)]) != slot)
        where = jnp.where(ends, slot, read(conv).shape[0])
        kept = read(conv).at[where].set(tail, mode="drop")
    else:
        kept = jnp.where((rows > 0)[:, None, None], tail, read(conv))
    conv.value = kept if cache_layer is None else conv.value.at[cache_layer].set(kept)


class SelectiveSSM(nn.Module):
    config: DecoderConfig
    mesh: Optional[Mesh] = None
    use_cache: bool = False
    decode: bool = False

    @nn.compact
    def __call__(self, x, cache_positions=None, ragged_slots=None, slot_hist=None,
                 kv_lengths=None, cache_layer=None):
        cfg = self.config
        e, d, n, r, k = cfg.embed_dim, cfg.ssm_inner_dim, cfg.ssm_state_dim, cfg.ssm_rank, cfg.ssm_conv_width
        dt_, f32 = cfg.dtype, jnp.float32
        b, s = x.shape[0], x.shape[1]
        dense = nn.initializers.variance_scaling(1.0, "fan_in", "normal")
        part = nn.with_logical_partitioning
        w_in = self.param("w_in", part(dense, ("embed", "mlp")), (e, 2 * d))
        conv_w = self.param("conv_w", part(dense, (None, "mlp")), (k, d))
        conv_b = self.param("conv_b", part(nn.initializers.zeros, ("mlp",)), (d,)) if cfg.ssm_conv_bias else None
        w_x = self.param("w_x", part(dense, ("mlp", None)), (d, r + 2 * n))
        w_dt = self.param("w_dt", part(dense, (None, "mlp")), (r, d))
        b_dt = self.param("b_dt", part(_inverse_softplus_log_uniform(), ("mlp",)), (d,), f32)
        a_log = self.param("a_log", part(_a_log_init, (None, "mlp")), (n, d), f32)
        d_skip = self.param("d_skip", part(nn.initializers.ones, ("mlp",)), (d,), f32)
        w_out = self.param("w_out", part(dense, ("mlp", "embed")), (d, e))
        norms = None
        if cfg.ssm_inner_norms:
            norms = [self.param(name, part(nn.initializers.ones, ("norm",)), (width,))
                     for name, width in (("norm_dt", r), ("norm_b", n), ("norm_c", n))]

        uz = x @ w_in.astype(dt_)
        u, z = uz[..., :d], uz[..., d:]

        serving = self.use_cache and self.decode and cache_positions is not None
        if serving and ragged_slots is None and s != 1:
            raise NotImplementedError(
                "a state-space layer decodes one token a slot: several would need "
                "the state rolled back on a rejected draft (ROADMAP R12)")
        state = conv = None
        if self.use_cache:
            state = self.variable("cache", "ssm_state", jnp.zeros, (b, n, d), f32)
            conv = self.variable("cache", "conv_state", jnp.zeros, (b, k - 1, d), dt_)
        stacked = cache_layer is not None
        read = lambda var: var.value[cache_layer] if stacked else var.value

        nb, bt, slot, rows, fresh, blocks, halo = _blocks_and_halo(
            cfg, u, conv, read, serving, self.use_cache and self.decode,
            cache_positions, ragged_slots, slot_hist, kv_lengths)
        ext = jnp.concatenate([halo.astype(dt_), blocks], axis=1)            # [nb, k-1+bt, d]
        acc = sum(ext[:, j:j + bt].astype(f32) * conv_w[j].astype(f32) for j in range(k))
        if conv_b is not None:
            acc = acc + conv_b.astype(f32)
        u_c = jax.nn.silu(acc)                                               # [nb, bt, d] float32

        # what feeds the recurrence stays float32 between the multiplications
        # (their inputs are cfg.dtype, their sums float32): the step, B and C
        # enter every token's update, and a rounding there is one per token
        mm = lambda v, w: jnp.matmul(v.astype(dt_), w.astype(dt_), preferred_element_type=f32)
        xp = mm(u_c, w_x)
        dlt, b_t, c_t = xp[..., :r], xp[..., r:r + n], xp[..., r + n:]
        if norms is not None:
            dlt, b_t, c_t = (rms_norm(v, w, cfg.norm_eps) for v, w in zip((dlt, b_t, c_t), norms))
        step = jax.nn.softplus(mm(dlt, w_dt) + b_dt)
        a = -jnp.exp(a_log.astype(f32))

        # -- the recurrence, and what the slot keeps --
        stack, layer = _stack_and_layer(state, cache_layer, (nb, n, d))
        y, stack = ssm_scan(
            u_c, step, b_t, c_t, a, d_skip, stack, block_slot=slot, block_rows=rows,
            block_fresh=fresh, layer=layer,
            # off the serving programs the jax.numpy form: it is the one that differentiates
            impl=cfg.ssm_kernel if serving else "reference")
        if state is not None:
            state.value = stack if stacked else stack[0]
            _keep_conv_tail(conv, read, ext, rows, slot, serving and ragged_slots is not None, cache_layer)

        out = (y * jax.nn.silu(z.reshape(nb, bt, d).astype(f32))).astype(dt_) @ w_out.astype(dt_)
        return out.reshape(b, s, e)


def _a_log_uniform(lo: float = 1.0, hi: float = 16.0):
    """``A = -a`` with ``a`` uniform in [lo, hi] a head, as Mamba-2 draws it."""
    def init(key, shape, dtype=jnp.float32):
        return jnp.log(jax.random.uniform(key, shape, jnp.float32, lo, hi)).astype(dtype)
    return init


class Mamba2Mixer(nn.Module):
    """The state-space mixer with heads (Mamba-2, as ``nemotron_h`` has it).
    ``H`` = ``ssm_num_heads``, ``P`` = ``ssm_head_dim``, ``D = H x P``, ``G`` =
    ``ssm_n_groups``, ``N`` = ``ssm_state_dim``:

        [z | xBC | dt] = h W_in                       (E -> D + (D + 2 G N) + H)
        xBC    = silu(conv(xBC) + b_conv)             causal, depthwise, over all D + 2 G N channels
        [x | B | C] = xBC                             x [H, P]; B, C [G, N]; head h reads group h // (H / G)
        dt     = softplus(dt + b_dt),  A = -exp(A_log)          one scalar a head each
        S_t[h] = exp(dt_t[h] A[h]) S_{t-1}[h] + dt_t[h] x_t[h] (x) B_t[g]
        y_t[h] = S_t[h] C_t[g] + D_skip[h] x_t[h]
        y      = RMSNorm within each of the G groups of D / G channels of (y * silu(z)), times a weight [D]
        out    = y W_out                              (D -> E)

    The step comes out of the input projection (no ``W_x``, no ``W_dt``, no
    inner norms). The three call forms, the blocks and what a slot keeps are
    :class:`SelectiveSSM`'s (the module's docstring); the recurrence is
    ``ops/ssm.ssd_scan``, float32, its state ``H x P x N`` a slot a layer."""

    config: DecoderConfig
    mesh: Optional[Mesh] = None
    use_cache: bool = False
    decode: bool = False

    @nn.compact
    def __call__(self, x, cache_positions=None, ragged_slots=None, slot_hist=None,
                 kv_lengths=None, cache_layer=None):
        cfg = self.config
        e, d, n, k = cfg.embed_dim, cfg.ssm_inner_dim, cfg.ssm_state_dim, cfg.ssm_conv_width
        h, p, g, cd = cfg.ssm_num_heads, cfg.ssm_head_dim, cfg.ssm_n_groups, cfg.ssm_conv_dim
        dt_, f32 = cfg.dtype, jnp.float32
        b, s = x.shape[0], x.shape[1]
        dense = nn.initializers.variance_scaling(1.0, "fan_in", "normal")
        part = nn.with_logical_partitioning
        w_in = self.param("w_in", part(dense, ("embed", "mlp")), (e, d + cd + h))
        conv_w = self.param("conv_w", part(dense, (None, "mlp")), (k, cd))
        conv_b = self.param("conv_b", part(nn.initializers.zeros, ("mlp",)), (cd,)) if cfg.ssm_conv_bias else None
        b_dt = self.param("b_dt", part(_inverse_softplus_log_uniform(), (None,)), (h,), f32)
        a_log = self.param("a_log", part(_a_log_uniform(), (None,)), (h,), f32)
        d_skip = self.param("d_skip", part(nn.initializers.ones, (None,)), (h,), f32)
        norm_w = self.param("norm_w", part(nn.initializers.ones, ("mlp",)), (d,))
        w_out = self.param("w_out", part(dense, ("mlp", "embed")), (d, e))

        # both projections take their input as two terms of the weights' dtype
        # (ops/layers.two_term_matmul): what this mixer rounds, the expert
        # layer behind it turns into another choice of 22 experts. Nothing is
        # rounded between the input projection and the recurrence, and the
        # convolution's kept inputs are float32 like the state
        zxd = two_term_matmul(x, w_in.astype(dt_))
        z, xbc, step = zxd[..., :d], zxd[..., d:d + cd], zxd[..., d + cd:]

        serving = self.use_cache and self.decode and cache_positions is not None
        if serving and ragged_slots is None and s != 1:
            raise NotImplementedError(
                "a state-space layer decodes one token a slot: several would need "
                "the state rolled back on a rejected draft (ROADMAP R12)")
        state = conv = None
        if self.use_cache:
            state = self.variable("cache", "ssm_state", jnp.zeros, (b, *ssd_state_shape(d, g, n)), f32)
            conv = self.variable("cache", "conv_state", jnp.zeros, (b, k - 1, cd), f32)
        stacked = cache_layer is not None
        read = lambda var: var.value[cache_layer] if stacked else var.value

        nb, bt, slot, rows, fresh, blocks, halo = _blocks_and_halo(
            cfg, xbc, conv, read, serving, self.use_cache and self.decode,
            cache_positions, ragged_slots, slot_hist, kv_lengths)
        ext = jnp.concatenate([halo, blocks], axis=1)                        # [nb, k-1+bt, cd]
        acc = sum(ext[:, j:j + bt].astype(f32) * conv_w[j].astype(f32) for j in range(k))
        if conv_b is not None:
            acc = acc + conv_b.astype(f32)
        xbc_c = jax.nn.silu(acc)                                             # [nb, bt, cd] float32
        x_c = xbc_c[..., :d]
        b_t = xbc_c[..., d:d + g * n].reshape(nb, bt, g, n)
        c_t = xbc_c[..., d + g * n:].reshape(nb, bt, g, n)
        step = jax.nn.softplus(step.reshape(nb, bt, h) + b_dt)
        a = -jnp.exp(a_log.astype(f32))
        a_head = lambda v: jnp.repeat(v, p, axis=-1)  # a head's scalar for each of its channels

        stack, layer = _stack_and_layer(state, cache_layer, (nb, *ssd_state_shape(d, g, n)))
        y, stack = ssd_scan(
            x_c, a_head(step), b_t, c_t, a_head(a), a_head(d_skip), stack, block_slot=slot,
            block_rows=rows, block_fresh=fresh, layer=layer,
            impl=cfg.ssm_kernel if serving else "reference")
        if state is not None:
            state.value = stack if stacked else stack[0]
            _keep_conv_tail(conv, read, ext, rows, slot, serving and ragged_slots is not None, cache_layer)

        # the gate first, then the norm within each group of D / G channels
        y = (y * jax.nn.silu(z.reshape(nb, bt, d))).reshape(nb, bt, g, d // g)
        y = y * jax.lax.rsqrt(jnp.mean(jnp.square(y), axis=-1, keepdims=True) + cfg.norm_eps)
        y = y.reshape(nb, bt, d) * norm_w.astype(f32)
        return two_term_matmul(y, w_out.astype(dt_)).reshape(b, s, e)  # float32, as the stream takes it


class GatedDeltaNet(nn.Module):
    """Gated DeltaNet (as ``qwen3_next`` has it). ``Hv`` = ``ssm_num_heads``
    value heads of ``dv`` = ``ssm_head_dim``, ``Hk`` = ``ssm_n_groups`` key
    heads of ``dk`` = ``ssm_state_dim``; value head ``h`` reads key head ``h //
    (Hv / Hk)``:

        [q | k | v | z] = h W_in                      (E -> Hk dk + Hk dk + Hv dv + Hv dv)
        [b | a] = h W_ba                              (E -> Hv + Hv)
        [q | k | v] = silu(conv([q | k | v]))         causal, depthwise, over all 2 Hk dk + Hv dv channels
        beta = sigmoid(b),  g = -exp(A_log) * softplus(a + b_dt)      one scalar a value head each, float32
        q = l2norm(q) * dk^-1/2,  k = l2norm(k)       within each head
        S <- exp(g_t) S;  r = S^T k_t;  S <- S + k_t (x) (beta_t (v_t - r));  o_t = S^T q_t     a value head
        o      = RMSNorm(o) * w * silu(z)             within each head of dv; the norm first, then the gate
        out    = o W_out                              (Hv dv -> E)

    The columns of ``W_in`` and ``W_ba`` are laid out flat, part by part (the
    published checkpoint interleaves them by key head: the adapter that loads
    it says how). The three call forms, the blocks and what a slot keeps are
    :class:`SelectiveSSM`'s (the module's docstring); the recurrence is
    ``ops/ssm.gdn_scan``, float32, its state ``Hv x dk x dv`` a slot a layer:
    a decode step walks its one row a slot, a pack's token blocks take the
    rule in chunked form on the matrix unit (the same kernel, by the block's
    shape)."""

    config: DecoderConfig
    mesh: Optional[Mesh] = None
    use_cache: bool = False
    decode: bool = False

    @nn.compact
    def __call__(self, x, cache_positions=None, ragged_slots=None, slot_hist=None,
                 kv_lengths=None, cache_layer=None):
        cfg = self.config
        e, d, dk, k = cfg.embed_dim, cfg.ssm_inner_dim, cfg.ssm_state_dim, cfg.ssm_conv_width
        hv, dv, hk, cd = cfg.ssm_num_heads, cfg.ssm_head_dim, cfg.ssm_n_groups, cfg.ssm_conv_dim
        kd = hk * dk
        dt_, f32 = cfg.dtype, jnp.float32
        b, s = x.shape[0], x.shape[1]
        dense = nn.initializers.variance_scaling(1.0, "fan_in", "normal")
        part = nn.with_logical_partitioning
        w_in = self.param("w_in", part(dense, ("embed", "mlp")), (e, cd + d))
        w_ba = self.param("w_ba", part(dense, ("embed", None)), (e, 2 * hv))
        conv_w = self.param("conv_w", part(dense, (None, "mlp")), (k, cd))
        conv_b = self.param("conv_b", part(nn.initializers.zeros, ("mlp",)), (cd,)) if cfg.ssm_conv_bias else None
        b_dt = self.param("b_dt", part(nn.initializers.ones, (None,)), (hv,), f32)
        a_log = self.param("a_log", part(_a_log_uniform(1e-3, 16.0), (None,)), (hv,), f32)
        norm_w = self.param("norm_w", part(nn.initializers.ones, ("norm",)), (dv,))
        w_out = self.param("w_out", part(dense, ("mlp", "embed")), (d, e))

        # the products' inputs are cfg.dtype, their sums float32; nothing is
        # rounded between the projections and the recurrence, and the
        # convolution's kept inputs are float32 like the state
        mm = lambda v, w: jnp.matmul(v.astype(dt_), w.astype(dt_), preferred_element_type=f32)
        qkvz, ba = mm(x, w_in), mm(x, w_ba)
        qkv, z = qkvz[..., :cd], qkvz[..., cd:]

        serving = self.use_cache and self.decode and cache_positions is not None
        if serving and ragged_slots is None and s != 1:
            raise NotImplementedError(
                "a layer with a recurrent state decodes one token a slot: several would need "
                "the state rolled back on a rejected draft (ROADMAP R12)")
        state = conv = None
        if self.use_cache:
            state = self.variable("cache", "ssm_state", jnp.zeros, (b, hv, dk, dv), f32)
            conv = self.variable("cache", "conv_state", jnp.zeros, (b, k - 1, cd), f32)
        stacked = cache_layer is not None
        read = lambda var: var.value[cache_layer] if stacked else var.value

        nb, bt, slot, rows, fresh, blocks, halo = _blocks_and_halo(
            cfg, qkv, conv, read, serving, self.use_cache and self.decode,
            cache_positions, ragged_slots, slot_hist, kv_lengths)
        ext = jnp.concatenate([halo, blocks], axis=1)                        # [nb, k-1+bt, cd]
        acc = sum(ext[:, j:j + bt].astype(f32) * conv_w[j].astype(f32) for j in range(k))
        if conv_b is not None:
            acc = acc + conv_b.astype(f32)
        qkv_c = jax.nn.silu(acc)                                             # [nb, bt, cd] float32
        unit = lambda v: v * jax.lax.rsqrt(jnp.sum(jnp.square(v), axis=-1, keepdims=True) + 1e-6)
        q = unit(qkv_c[..., :kd].reshape(nb, bt, hk, dk)) * dk ** -0.5
        k_ = unit(qkv_c[..., kd:2 * kd].reshape(nb, bt, hk, dk))
        v = qkv_c[..., 2 * kd:].reshape(nb, bt, hv, dv)
        ba = ba.reshape(nb, bt, 2 * hv)
        beta = jax.nn.sigmoid(ba[..., :hv])
        g = -jnp.exp(a_log.astype(f32)) * jax.nn.softplus(ba[..., hv:] + b_dt)

        packed = serving and ragged_slots is not None
        stack, layer = _stack_and_layer(state, cache_layer, (nb, hv, dk, dv))
        o, stack = gdn_scan(
            q, k_, v, g, beta, stack, block_slot=slot, block_rows=rows, block_fresh=fresh, layer=layer,
            impl=cfg.ssm_kernel if serving else "reference")
        if state is not None:
            state.value = stack if stacked else stack[0]
            _keep_conv_tail(conv, read, ext, rows, slot, packed, cache_layer)

        # the norm within each head first, then the gate
        o = o * jax.lax.rsqrt(jnp.mean(jnp.square(o), axis=-1, keepdims=True) + cfg.norm_eps)
        o = o * norm_w.astype(f32) * jax.nn.silu(z.reshape(nb, bt, hv, dv))
        return mm(o.reshape(nb, bt, d), w_out).reshape(b, s, e)  # float32, as the stream takes it

"""Sharded training steps: ``Accelerator`` -> ``prepare(model, optimizer,
dataloader)`` -> ``build_train_step()``, fed by the prepared loader.

Set-up builds ONE object, the compiled step with its state, drives it from
the seed through its first steps (which the reference follows once the
window has closed) and hands that same object to the window. Rows come from
the benchmark's own dataset, all different, made from ``--seed``.
"""

from __future__ import annotations

import gc
import time

import numpy as np

import weights
from reference import train as ref_train


class PackedRows:
    """Row i is ``sequence_length`` token ids drawn from (seed, i)."""

    def __init__(self, seed: int, vocab: int, seq_len: int, rows: int):
        self.seed, self.vocab, self.seq_len, self.rows = int(seed), vocab, seq_len, rows

    def __len__(self):
        return self.rows

    def __getitem__(self, i):
        ids = np.random.default_rng([self.seed, 4, int(i)]).integers(0, self.vocab, self.seq_len, dtype=np.int32)
        return {"input_ids": ids, "labels": ids}


def build(ctx):
    """-> (accelerator, model, optimizer, loader iterator, step, param shardings)."""
    import jax
    import jax.numpy as jnp
    import optax

    from accelerate_tpu import Accelerator, Model
    from accelerate_tpu.data import DataLoader
    from accelerate_tpu.parallel.sharding import infer_param_sharding, unbox_params
    from accelerate_tpu.state import AcceleratorState
    from accelerate_tpu.utils.dataclasses import ShardingConfig

    arch, c, t, traffic = ctx.arch, ctx.settings, ctx.settings["training"], ctx.traffic
    AcceleratorState._reset_state(reset_partial_state=True)
    sharding = ShardingConfig(fsdp=t["fsdp"], tensor_parallel=t["tensor_parallel"])
    accelerator = Accelerator(mixed_precision=t["mixed_precision"], sharding_config=sharding)
    cfg = arch.decoder_config(
        c, max_seq_len=traffic["sequence_length"], remat=t["remat"], remat_policy=t["remat_policy"],
        **({"attention_impl": "xla"} if ctx.rehearsal else {}))
    model_def = arch.module(cfg, mesh=accelerator.mesh)
    boxed = jax.eval_shape(lambda k: model_def.init(k, jnp.zeros((1, 8), jnp.int32)), jax.random.PRNGKey(0))["params"]
    raw, axes = unbox_params(boxed)
    shardings = infer_param_sharding(raw, accelerator.mesh, sharding, axes)
    params = weights.make_jit(arch.reference, c, ctx.seed, jnp.float32, adapt=arch.to_program_tree(c),
                              out_shardings=shardings)
    is_boxed = lambda l: hasattr(l, "unbox")
    variables = {"params": jax.tree_util.tree_map(
        lambda box, value: box.replace_boxed(value) if is_boxed(box) else value, boxed, params, is_leaf=is_boxed)}
    tx = optax.adamw(t["learning_rate"], b1=t["b1"], b2=t["b2"], eps=t["eps"], weight_decay=t["weight_decay"])
    rows = PackedRows(ctx.seed, arch.vocab(c), traffic["sequence_length"], traffic["sequences_per_step"] * 100_000)
    loader = DataLoader(rows, batch_size=traffic["sequences_per_step"])
    model, optimizer, loader = accelerator.prepare(Model(model_def, variables), tx, loader)
    del params, variables
    return accelerator, model, optimizer, iter(loader), accelerator.build_train_step(), shardings


def program_leaf_norms(arch, c, tree) -> dict:
    import jax

    norms = jax.jit(lambda p: ref_train.leaf_norms(arch.reference, arch.from_program_tree(c, p)))(tree)
    return {k: float(v) for k, v in norms.items()}


def run(ctx) -> dict:
    import jax
    import jax.numpy as jnp

    from accelerate_tpu.utils.compile_cache import compile_event_counters

    arch, c, t, traffic = ctx.arch, ctx.settings, ctx.settings["training"], ctx.traffic
    tokens_per_step = traffic["sequences_per_step"] * traffic["sequence_length"]
    accelerator, model, optimizer, batches, step, shardings = build(ctx)
    ctx.say(f"prepared: mesh {accelerator.state.mesh_shape}, {arch.total_params(c) / 1e9:.3f}B parameters, "
            f"{tokens_per_step} tokens a step, {time.perf_counter() - ctx.t_start:.1f}s since start")

    def one_step():
        with ctx.spans.span("bench/next_batch"):
            batch = next(batches)
        with ctx.spans.span("bench/step"):
            loss = float(jax.block_until_ready(step(batch)["loss"]))
        return loss

    # the first steps, through the window's own call and feed; the reference
    # follows them later from the same seed
    n_ref = int(traffic["reference_steps"])
    first_losses, grad_norms = [], None
    for n in range(max(n_ref, int(traffic["warm_steps"]))):
        first_losses.append(one_step())
        if n == 0:  # Adam's first moment after one step is (1 - b1) * gradient
            mu = [s.mu for s in jax.tree_util.tree_leaves(
                optimizer.state, is_leaf=lambda s: hasattr(s, "mu")) if hasattr(s, "mu")][0]
            grad_norms = {k: v / (1 - t["b1"]) for k, v in program_leaf_norms(arch, c, mu).items()}
        if n == n_ref - 1:
            p0 = weights.make_jit(arch.reference, c, ctx.seed, jnp.float32, adapt=arch.to_program_tree(c),
                                  out_shardings=shardings)
            delta = jax.jit(lambda a, b: jax.tree_util.tree_map(lambda x, y: x - y, a, b))(model.params, p0)
            update_norms = program_leaf_norms(arch, c, delta)
            del p0, delta
    ctx.setup_done()

    compiles0 = compile_event_counters()["count"]
    t0 = time.perf_counter()
    losses, ends = [], []
    untraced = ctx.seconds - (min(ctx.trace_seconds, ctx.seconds / 2) if ctx.trace else 0.0)
    while time.perf_counter() - t0 < untraced:
        losses.append(one_step())
        ends.append(time.perf_counter())
    n_window = len(losses)
    traced = None
    if ctx.trace:
        ctx.start_trace()
        t_tr = time.perf_counter()
        while time.perf_counter() - t_tr < ctx.seconds - untraced:
            losses.append(one_step())
        traced = {"steps": len(losses) - n_window}
        ctx.stop_trace()
    compiles = compile_event_counters()["count"] - compiles0
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in jax.local_devices())
    window_s = ends[-1] - t0
    rate = n_window * tokens_per_step / window_s
    bad = [l for l in losses if not np.isfinite(l)]
    ctx.say(f"window {window_s:.3f}s, {n_window} steps, {rate:.1f} tokens/s, loss {losses[0]:.4f} -> "
            f"{losses[n_window - 1]:.4f}, {len(bad)} non-finite, {compiles} compiles in the window")
    counters = {"kind": "train", "window_s": window_s, "steps": n_window, "train_tokens_per_s": rate,
                "flops_per_token": arch.train_flops_per_token(c, traffic["sequence_length"]),
                "next_batch_s": ctx.spans.total("bench/next_batch", since=t0, until=ends[-1]),
                "compiles_in_window": compiles, "traced": traced}

    # free the program's state, then let the reference follow the first steps
    accelerator.free_memory()
    del accelerator, model, optimizer, batches, step
    gc.collect()
    jax.clear_caches()
    program = {"losses": first_losses[:n_ref], "grad_norms": grad_norms, "update_norms": update_norms}
    check = compare(ctx, program, shardings)
    # finite and not rising: the window's last quarter against the first
    # steps. Rows are random ids, so all there is to learn is that every id is
    # as likely as any other; two percent covers a small batch's noise (0.4% a
    # step at the rehearsal's size) and is far under what a diverging run shows.
    tail = losses[n_window - max(1, n_window // 4):n_window]
    falling = sum(tail) / len(tail) < 1.02 * sum(first_losses[:n_ref]) / n_ref
    ctx.say(f"loss of the first {n_ref} steps {sum(first_losses[:n_ref]) / n_ref:.4f}, of the window's last "
            f"{len(tail)} {sum(tail) / len(tail):.4f} ({'not rising' if falling else 'RISING'})")
    ok = check["ok"] and compiles == 0 and not bad and falling
    compared = {k: (v, ctx.limits[k]) for k, v in check["numbers"].items()}
    compared.update(compiles_in_window=(compiles, 0), non_finite_losses=(len(bad), 0),
                    loss_last_quarter_over_first_steps=(sum(tail) / len(tail) / (sum(first_losses[:n_ref]) / n_ref), 1.02))
    return {"values": {"train_tokens_per_s": rate}, "counters": counters, "attempted": n_window,
            "failed": len(bad), "correct": ok, "memory_peak_bytes": int(peak), "check": check, "compared": compared}


def compare(ctx, program: dict, shardings) -> dict:
    """Each step's loss, the first gradient's norm and the norm of the
    parameters' change after the followed steps, the last two by the worst
    leaf, each against its own limit."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    c, t, traffic, lim = ctx.settings, ctx.settings["training"], ctx.traffic, ctx.limits
    reference = ctx.arch.reference
    t0 = time.perf_counter()
    mesh = jax.sharding.Mesh(np.array(jax.devices()), ("x",))
    shard = {k: NamedSharding(mesh, P(*([None] * (len(s) - 1 + (k in reference.LAYER_LEAVES)) + ["x"])))
             if (s[-1] % len(jax.devices()) == 0 and len(s) > 1) else NamedSharding(mesh, P())
             for k, s in reference.shapes(c).items()}
    make_w0 = lambda: weights.make_jit(reference, c, ctx.seed, jnp.float32, out_shardings=shard)
    rows = PackedRows(ctx.seed, ctx.arch.vocab(c), traffic["sequence_length"], 1 << 30)
    n, b = len(program["losses"]), traffic["sequences_per_step"]
    # groups of one row a device, taken one after another
    per = len(jax.devices()) if b % len(jax.devices()) == 0 else b
    row_sharding = NamedSharding(mesh, P(None, "x" if per == len(jax.devices()) else None, None))
    batches = [jax.device_put(np.stack([rows[s * b + i]["input_ids"] for i in range(b)]).reshape(b // per, per, -1),
                              row_sharding) for s in range(n)]
    # hints only: a weight is gathered whole just before its use and the
    # rows stay one to a device, so the multiplications are local (left to the
    # partitioner, the rows travelled instead: 65 s a step, PR 23)
    whole, by_row = NamedSharding(mesh, P()), NamedSharding(mesh, P("x" if per == len(jax.devices()) else None))
    place = lambda x, kind: jax.lax.with_sharding_constraint(x, whole if kind == "weight" else by_row)
    results = {}
    for name, precision in [("reference", "float32")] + ([("control", ctx.control)] if ctx.control else []):
        results[name] = ref_train.follow(reference, c, t, make_w0, batches, precision, place, ctx.say)
    ref = results["reference"]

    def numbers(got):
        g_gap, g_leaf = ref_train.worst_leaf_gap(got["grad_norms"], ref["grad_norms"])
        u_gap, u_leaf = ref_train.worst_leaf_gap(got["update_norms"], ref["update_norms"])
        return {"loss_abs": max(abs(a - b) for a, b in zip(got["losses"], ref["losses"])),
                "grad_norm_rel": g_gap, "update_norm_rel": u_gap}, (g_leaf, u_leaf)

    got, leaves = numbers(program)
    ok = all(got[k] <= lim[k] for k in got)
    took = time.perf_counter() - t0
    ctx.say(f"compared over {n} steps: losses {['%.5f' % l for l in program['losses']]} against "
            f"{['%.5f' % l for l in ref['losses']]}; " + "; ".join(
                f"{k} {got[k]:.6f} limit {lim[k]}" for k in got)
            + f" ({'ok' if ok else 'NOT CORRECT'}; worst leaves {leaves}); reference took {took:.1f}s")
    control = None
    if ctx.control:
        control, _ = numbers(results["control"])
        fails = [k for k in control if control[k] > lim[k]]
        ctx.say(f"control ({ctx.control} reference in the program's place): " + "; ".join(
            f"{k} {control[k]:.6f} limit {lim[k]}" for k in control)
            + (f" (fails {fails}, as it must)" if fails else " (PASSES: the limits are too loose)"))
    return {"ok": ok, "numbers": got, "control": control, "reference_s": took}

"""``benchmarks/run.py`` end to end at tiny widths on the CPU, the control
that has to come out as not correct, and the timed path broken underneath."""

import json
import os
import subprocess
import sys
import types

import numpy as np
import pytest

import cells
import manifest as M

SERVE, TRAIN = cells.by_driver("closed_loop"), cells.by_driver("train_steps")
RUN = os.path.join(M.BENCH_DIR, "run.py")
PREFIX = "[CPU REHEARSAL, not a chip run] "
KEYS = {"correct", "attempted", "failed", "metrics", "device", "compared"}


def _run(*extra, workload, trace=0, seconds=3):
    env = dict(os.environ, PYTHONPATH=M.ROOT)
    env.pop("JAX_DISABLE_MOST_OPTIMIZATIONS", None)  # the suite's own setting; see conftest.optimized_xla
    return subprocess.run(
        [sys.executable, RUN, "--workload", workload, "--seed", "3000000019", "--seconds", str(seconds),
         "--trace", str(trace), *extra], capture_output=True, text=True, env=env, cwd=M.ROOT, timeout=600)


def _last(proc):
    lines = [l for l in proc.stdout.splitlines() if l.strip()]
    assert all(l.startswith(PREFIX) for l in lines), proc.stdout[-2000:]
    return json.loads(lines[-1][len(PREFIX):]), lines


@pytest.mark.parametrize("workload,trace", [(w, int(i == 0)) for i, w in enumerate(cells.all_cells())])
def test_rehearsal_prints_the_contracts_line(workload, trace):
    """Every cell of the manifest, the first of them traced."""
    proc = _run("--cpu-rehearsal", workload=workload, trace=trace)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line, lines = _last(proc)
    assert set(line) == KEYS | ({"breakdown"} if trace else set())
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    assert set(line["device"]) >= {"platform", "kind", "count", "memory_peak_bytes"}
    assert line["device"]["platform"] == "cpu"
    assert line["device"]["count"] == cells.find(workload)["chips"]
    # never a metric under its name
    assert line["metrics"] and all(k.startswith("rehearsal:") for k in line["metrics"])
    man = M.load_manifest()
    names = {m["name"] for m in man["per_layer" if trace else "end_to_end"]}
    assert {k.split(":", 1)[1] for k in line["metrics"]} <= names
    if trace:
        assert line["device"]["busy_s"] > 0 and line["device"]["window_s"] > 0
        assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
    else:
        assert "rehearsal:setup_s" in line["metrics"]
    # every number compared is printed beside its limit: in the log, last in the line, last on standard error
    assert any("limit" in l and "compared" in l for l in lines)
    assert list(line)[-1] == "compared" and len(line["compared"]) >= 3
    assert all(set(c) == {"value", "limit"} for c in line["compared"].values())
    assert proc.stderr.rstrip().splitlines()[-1].startswith(PREFIX + "compared ")


def test_without_the_chip_there_is_no_result():
    proc = _run(workload=SERVE[0])
    assert proc.returncode != 0
    assert '"correct"' not in proc.stdout and "need" in proc.stderr


def _context(workload, seed=5, **config_overrides):
    import run as R

    # a serving window of 60 iterations, not of seconds: the same requests are compared on every machine
    args = types.SimpleNamespace(seed=seed, seconds=2.0, iterations=60, trace=0, cpu_rehearsal=True, control=None)
    cell = cells.find(workload)
    for group, values in config_overrides.items():
        cell["config_values"]["rehearsal"].setdefault(group, {}).update(values)
    return R.Context(cell, args)


def test_sound_engine_is_correct_and_a_lower_precision_engine_is_not(optimized_xla):
    """The engine built with a quantized KV cache where the configuration
    states bfloat16 serves tokens whose logits lie further below the
    reference's best than the limit allows. At the test's two layers of 256
    the engine's int8 cache (a float32 scale a token and head) moves a logit
    less than bfloat16 rounding does, so the test takes the int4 cache; the
    control at the cell's own size is the fp8 reference (PERF.md)."""
    driver = M.load_driver("closed_loop")
    gap = lambda out: out["check"]["numbers"]["served_logit_gap"][0]
    sound = driver.run(_context(SERVE[0]))
    assert sound["correct"] is True and gap(sound) <= 0.02
    low = driver.run(_context(SERVE[0], serving={"engine_kwargs": {"kv_cache_dtype": "int4"}}))
    print("sound", gap(sound), "int4 cache", gap(low))
    assert low["correct"] is False
    assert gap(low) > 3 * max(gap(sound), 0.01)


def test_a_token_altered_where_it_is_produced_is_not_correct(monkeypatch, optimized_xla):
    """The rest of a run, with the timed path broken underneath: every
    fifth emitted token is replaced by its neighbour in the vocabulary."""
    from accelerate_tpu.serving import ServingEngine

    emit, count = ServingEngine._emit, [0]

    def broken(self, req, token, now):
        count[0] += 1
        return emit(self, req, (token + 1) % 512 if count[0] % 5 == 0 else token, now)

    monkeypatch.setattr(ServingEngine, "_emit", broken)
    out = M.load_driver("closed_loop").run(_context(SERVE[1]))
    assert out["correct"] is False and out["failed"] == 0


def test_training_step_that_leaves_its_state_unchanged_is_not_correct(monkeypatch, optimized_xla):
    """The four-chip cell's driver on the test's CPU devices, the step broken
    underneath: the optimizer's update is dropped, so parameters never move."""
    import optax

    real = optax.adamw

    def frozen(*a, **k):
        tx = real(*a, **k)
        return optax.GradientTransformation(
            tx.init, lambda g, s, p=None: (lambda u, s2: (optax.tree_utils.tree_scale(0.0, u), s2))(*tx.update(g, s, p)))

    monkeypatch.setattr(optax, "adamw", frozen)
    out = M.load_driver("train_steps").run(_context(TRAIN[0]))
    assert out["correct"] is False
    assert out["check"]["numbers"]["update_norm_rel"] > 0.9


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_control_training_in_fp8_fails_a_limit(seed, optimized_xla):
    """The control, kept at a size a test can hold: the reference computed in
    fp8 in the program's place has to fail at least one of the cell's numbers
    at the rehearsal's limits, where the program itself passes them."""
    import run as R

    args = types.SimpleNamespace(seed=seed, seconds=1.0, trace=0, cpu_rehearsal=True, control="fp8")
    ctx = R.Context(cells.find(TRAIN[0]), args)
    out = M.load_driver("train_steps").run(ctx)
    assert out["correct"] is True
    lim, control = ctx.limits, out["check"]["control"]
    assert any(control[k] > lim[k] for k in control), (control, lim)
    assert all(out["check"]["numbers"][k] <= lim[k] / 2 for k in lim), out["check"]["numbers"]


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_control_serving_in_fp8_fails_the_limit(seed, optimized_xla):
    import run as R

    args = types.SimpleNamespace(seed=seed, seconds=2.0, iterations=60, trace=0, cpu_rehearsal=True, control="fp8")
    ctx = R.Context(cells.find(SERVE[0]), args)
    out = M.load_driver("closed_loop").run(ctx)
    assert out["correct"] is True
    (control, limit), (sound, _) = out["check"]["control"]["served_logit_gap"], out["check"]["numbers"]["served_logit_gap"]
    assert control > limit == ctx.limits["served_logit_gap"] >= 2 * sound


def test_layer_by_layer_gradient_is_the_whole_models(optimized_xla):
    """The training reference takes its gradient one layer at a time at the
    timed size; at a size a test can hold it is jax.grad of the whole loss."""
    import jax
    import jax.numpy as jnp

    import weights
    from reference import train as ref_train

    model = _context(TRAIN[0]).arch.reference
    c = {"hidden_size": 128, "intermediate_size": 256, "num_attention_heads": 4, "num_key_value_heads": 2,
         "head_dim": 32, "vocab_size": 256, "num_hidden_layers": 3, "rope_theta": 1e6, "rms_norm_eps": 1e-5}
    w = weights.make_jit(model, c, 11, jnp.float32)
    ids = jnp.asarray(np.random.default_rng(0).integers(0, 256, (2, 2, 64), dtype=np.int32))
    loss, grads = jax.jit(lambda p, i: ref_train.loss_and_grad(model, c, "float32", p, i))(w, ids)
    loss2, grads2 = ref_train.LayerByLayer(model, c, "float32")(w, ids)
    assert float(loss2) == pytest.approx(float(loss), rel=1e-6)
    for k in grads:
        scale = float(jnp.max(jnp.abs(grads[k])))
        assert float(jnp.max(jnp.abs(grads[k] - grads2[k]))) <= 1e-5 * scale, k

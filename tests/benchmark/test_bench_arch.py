"""The one seam by architecture: a configuration of another architecture
arrives as new files and manifest entries only, and the architecture that
stands reaches the drivers through the seam with the weights it had."""

import hashlib
import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

import manifest as M
import published_widths

HERE = os.path.dirname(os.path.abspath(__file__))
TOY = os.path.join(HERE, "data", "toy_arch")
PREFIX = "[CPU REHEARSAL, not a chip run] "
SEAM = {"vocab", "kv_bytes_per_token", "decode_kv_bytes", "decoder_config", "module", "to_program_tree"}
TRAINED = {"matmul_params", "total_params", "train_flops_per_token", "from_program_tree"}
REFERENCE = {"LAYER_LEAVES", "shapes", "layer", "head_logits", "logits_at"}


def _hashes(root) -> dict:
    out = {}
    for base, dirs, files in os.walk(root):
        dirs[:] = [d for d in dirs if d != "__pycache__"]
        for name in files:
            path = os.path.join(base, name)
            with open(path, "rb") as f:
                out[os.path.relpath(path, root)] = hashlib.sha256(f.read()).hexdigest()
    return out


def test_a_configuration_of_another_architecture_is_files_only(tmp_path):
    """In a copy of the benchmark: the toy architecture's seam module,
    reference, public-values file, configuration, traffic file and cell
    entry. No file that was there changes, ``find_cell`` and the seam resolve
    it, its configuration is held to its own public values, and ``run.py``
    runs the cell to a correct contracts line."""
    root = tmp_path / "checkout"
    man = M.load_manifest()
    for path in man["paths"]:
        shutil.copytree(os.path.join(M.ROOT, path), root / path, ignore=shutil.ignore_patterns("__pycache__"))
    before = _hashes(root)
    with open(os.path.join(TOY, "config.json")) as f:
        model_type = json.load(f)["model_type"]
    bench, tests = root / man["paths"][0], root / man["paths"][1]
    for src, dst in (("arch.py", bench / "arch" / f"{model_type}.py"),
                     ("reference.py", bench / "reference" / f"{model_type}.py"),
                     ("config.json", bench / "configs" / "toy-fused-qkv-4l.json"),
                     ("traffic.json", bench / "traffic" / "toy-chat.json"),
                     ("published.json", tests / "data" / "published" / f"{model_type}.json")):
        shutil.copy(os.path.join(TOY, src), dst)
    standing = json.loads(json.dumps(man))
    cell = "toy_fused_qkv_chat"
    man["configs"].append({"name": "toy-fused-qkv-4l", "source": "https://example.org/toy-fused-qkv/config.json",
                           "file": f"{man['paths'][0]}/configs/toy-fused-qkv-4l.json",
                           "reduced": ["num_hidden_layers", "vocab_size"], "why": "the seam's test"})
    man["workloads"].append({"name": cell, "config": "toy-fused-qkv-4l", "traffic": "toy-chat", "chips": 1,
                             "why": "the seam's test"})
    # the cell joins metrics that stand: their workloads lists gain it
    joined = [m for m in man["end_to_end"] + man["per_layer"]
              if m["name"] in ("out_tokens_per_s", "kv_pages_peak_pct", "prefix_hit_share_pct")]
    assert len(joined) == 3
    for m in joined:
        m["workloads"].append(cell)
    (root / "BENCHMARK.json").write_text(json.dumps(man))

    after = _hashes(root)
    assert {k: after[k] for k in before} == before and len(after) == len(before) + 6
    for section in ("configs", "workloads", "end_to_end", "per_layer"):  # what stood is there, unchanged
        for old, new in zip(standing[section], man[section]):
            rest = lambda e: {k: v for k, v in e.items() if k != "workloads"}
            assert rest(new) == rest(old)
            assert new.get("workloads", [])[:len(old.get("workloads", []))] == old.get("workloads", [])

    found = M.find_cell(M.load_manifest(str(root)), cell, str(root))
    assert found["config_values"]["model_type"] == model_type and found["traffic_values"]["clients"] == 3
    arch = M.load_arch(model_type, found["bench_dir"])
    assert SEAM <= set(dir(arch)) and REFERENCE <= set(dir(arch.reference))
    assert "qkv" in arch.reference.shapes(found["config_values"])
    published_widths.check(found["config_values"], found["config_entry"],
                           published_widths.load_public(model_type, str(tests / "data" / "published")))

    env = dict(os.environ, PYTHONPATH=M.ROOT)  # the program comes from the repo; the benchmark from the copy
    env.pop("JAX_DISABLE_MOST_OPTIMIZATIONS", None)
    proc = subprocess.run([sys.executable, str(bench / "run.py"), "--workload", cell, "--seed", "3000000023",
                           "--seconds", "3", "--trace", "0", "--cpu-rehearsal"],
                          capture_output=True, text=True, cwd=str(root), env=env, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = [l for l in proc.stdout.splitlines() if l.strip()]
    line = json.loads(lines[-1][len(PREFIX):])
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    assert set(line["metrics"]) == {"rehearsal:out_tokens_per_s", "rehearsal:setup_s"}
    assert any("compared" in l and "limit" in l for l in lines)


def _architectures():
    seen = set()
    for entry in M.load_manifest()["configs"]:
        with open(os.path.join(M.ROOT, entry["file"])) as f:
            seen.add(json.load(f)["model_type"])
    return sorted(seen)


@pytest.mark.parametrize("model_type", _architectures())
def test_the_seam_gives_what_the_drivers_ask(model_type):
    arch = M.load_arch(model_type)
    assert SEAM | TRAINED <= set(dir(arch)) and REFERENCE <= set(dir(arch.reference))
    # the reference side imports nothing of the program and nothing of the adapter
    with open(arch.reference.__file__) as f:
        source = f.read()
    assert "accelerate_tpu" not in source and "import arch" not in source and "load_arch" not in source
    with pytest.raises(FileNotFoundError, match="no arch for model_type"):
        M.load_arch("an-architecture-nobody-brought")


def _parent_weights():
    with open(os.path.join(HERE, "data", "weights_parent.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("case", sorted(_parent_weights()))
def test_weights_through_the_seam_are_the_parents(case, optimized_xla):
    """A checksum of every leaf at the rehearsal's size, taken on the parent
    commit (``weights.make_jit`` before the layout moved into the seam): the
    keys that make a leaf did not change, so the weights are bit for bit what
    they were, for a seed past 2**31 too. (With most XLA optimizations off, as
    the suite runs, the normal draws round differently: hence the fixture.)"""
    import jax.numpy as jnp

    import weights

    config, dtype, seed = case.split("/")
    (entry,) = [c for c in M.load_manifest()["configs"] if c["name"] == config]
    with open(os.path.join(M.ROOT, entry["file"])) as f:
        c = json.load(f)
    c.update({k: v for k, v in c.pop("rehearsal").items() if not isinstance(v, dict)})
    w = weights.make_jit(M.load_arch(c["model_type"]).reference, c, int(seed), getattr(jnp, dtype))
    got = {k: hashlib.sha256(np.asarray(v).tobytes()).hexdigest()[:16] for k, v in w.items()}
    assert got == _parent_weights()[case]


def test_the_harness_names_no_architecture():
    """Drivers, the training reference, the command, the finders and the
    shared arithmetic name no architecture and branch on none; these tests
    name none either. What an architecture owns is under its ``model_type``."""
    names, gone = _architectures(), "program_" + "adapter"
    bench = M.BENCH_DIR
    files = [os.path.join(bench, "drivers", f) for f in os.listdir(os.path.join(bench, "drivers")) if f.endswith(".py")]
    files += [os.path.join(bench, f) for f in ("reference/train.py", "run.py", "manifest.py", "metriclib.py",
                                               "weights.py", "costs.py", "traffic_gen.py")]
    files += [os.path.join(HERE, f) for f in os.listdir(HERE) if f.startswith("test_") and f.endswith(".py")]
    for path in files:
        with open(path) as f:
            text = f.read().lower()
        for name in names:
            assert name.lower() not in text, (path, name)
        assert "model_type" + " ==" not in text and gone not in text, path
    assert not os.path.exists(os.path.join(bench, gone + ".py"))

"""Examples run end-to-end on the CPU sim + the examples-diff machinery.

Parity: reference tests/test_examples.py — it (a) runs every example script,
and (b) asserts the by_feature/complete scripts stay in sync with the base
example outside their feature blocks (the "examples diff" machinery). Here
(b) is structural: the feature scripts must reuse the base example's data
pipeline (import, not copy) and keep the same eval contract.
"""

from __future__ import annotations

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
EXAMPLES = os.path.join(REPO, "examples")


def _run_example(rel_path, *extra, timeout=420):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    return subprocess.run(
        [sys.executable, os.path.join(EXAMPLES, rel_path), "--cpu", "--num_epochs", "1", *extra],
        capture_output=True,
        text=True,
        timeout=timeout,
        env=env,
        cwd=REPO,
    )


def _run_inference_example(rel_path, *extra, timeout=420):
    """Inference examples take --cpu/--tiny but no training args."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    return subprocess.run(
        [sys.executable, os.path.join(EXAMPLES, rel_path), "--cpu", "--tiny", *extra],
        capture_output=True, text=True, timeout=timeout, env=env, cwd=REPO,
    )


@pytest.mark.slow
class TestExamplesRun:
    def test_nlp_example(self):
        r = _run_example("nlp_example.py")
        assert r.returncode == 0, r.stderr
        assert "accuracy" in r.stdout

    def test_cv_example(self):
        r = _run_example("cv_example.py")
        assert r.returncode == 0, r.stderr
        assert "accuracy" in r.stdout

    def test_seq2seq_example(self):
        r = _run_example("seq2seq_example.py")
        assert r.returncode == 0, r.stderr
        assert "reversal_accuracy" in r.stdout

    def test_grad_compression_example(self):
        r = _run_example(os.path.join("by_feature", "grad_compression.py"))
        assert r.returncode == 0, r.stderr
        assert "accuracy" in r.stdout and "grad_norm" in r.stdout

    @pytest.mark.slow
    def test_pipeline_example_1f1b(self):
        r = _run_example(os.path.join("by_feature", "pipeline.py"),
                         "--schedule", "1f1b")
        assert r.returncode == 0, r.stderr
        assert "'final_loss'" in r.stdout

    def test_peak_memory_tracking_example(self):
        r = _run_example(os.path.join("by_feature", "peak_memory_tracking.py"))
        assert r.returncode == 0, r.stderr
        assert "accuracy" in r.stdout
        assert "peak device memory" in r.stdout or "memory stats" in r.stdout

    def test_gradient_accumulation_example(self):
        r = _run_example(os.path.join("by_feature", "gradient_accumulation.py"),
                         "--gradient_accumulation_steps", "2")
        assert r.returncode == 0, r.stderr
        assert "accuracy" in r.stdout

    def test_tracking_example(self, tmp_path):
        r = _run_example(os.path.join("by_feature", "tracking.py"),
                         "--project_dir", str(tmp_path))
        assert r.returncode == 0, r.stderr
        assert "accuracy" in r.stdout

    def test_checkpointing_example_rotates(self, tmp_path):
        r = _run_example(os.path.join("by_feature", "checkpointing.py"),
                         "--num_epochs", "3", "--project_dir", str(tmp_path))
        assert r.returncode == 0, r.stderr
        ckpts = sorted(os.listdir(tmp_path / "checkpoints"))
        assert len(ckpts) == 2, ckpts  # total_limit=2 evicted the oldest
        r2 = _run_example(
            os.path.join("by_feature", "checkpointing.py"),
            "--project_dir", str(tmp_path / "resume_run"),
            "--resume_from_checkpoint", str(tmp_path / "checkpoints" / ckpts[-1]),
        )
        assert r2.returncode == 0, r2.stderr

    def test_local_sgd_example(self):
        r = _run_example(os.path.join("by_feature", "local_sgd.py"),
                         "--local_sgd_steps", "2")
        assert r.returncode == 0, r.stderr
        assert "accuracy" in r.stdout

    def test_memory_example(self):
        r = _run_example(os.path.join("by_feature", "memory.py"))
        assert r.returncode == 0, r.stderr
        assert "accuracy" in r.stdout

    def test_early_stopping_example(self):
        r = _run_example(os.path.join("by_feature", "early_stopping.py"),
                         "--num_epochs", "4", "--patience", "1")
        assert r.returncode == 0, r.stderr
        assert "accuracy" in r.stdout

    def test_profiler_example(self, tmp_path):
        r = _run_example(os.path.join("by_feature", "profiler.py"),
                         "--trace_dir", str(tmp_path / "traces"))
        assert r.returncode == 0, r.stderr
        assert "trace written" in r.stdout

    def test_multi_process_metrics_example(self):
        r = _run_example(os.path.join("by_feature", "multi_process_metrics.py"))
        assert r.returncode == 0, r.stderr
        assert "accuracy" in r.stdout and "examples" in r.stdout

    def test_automatic_gradient_accumulation_example(self):
        r = _run_example(os.path.join("by_feature", "automatic_gradient_accumulation.py"))
        assert r.returncode == 0, r.stderr
        assert "accuracy" in r.stdout

    def test_schedule_free_example(self):
        r = _run_example(os.path.join("by_feature", "schedule_free.py"))
        assert r.returncode == 0, r.stderr
        assert "accuracy" in r.stdout

    def test_cross_validation_example(self):
        r = _run_example(os.path.join("by_feature", "cross_validation.py"),
                         "--num_folds", "2", "--num_epochs", "1")
        assert r.returncode == 0, r.stderr
        assert "ensemble test accuracy" in r.stdout

    def test_complete_cv_example(self, tmp_path):
        r = _run_example(
            "complete_cv_example.py",
            "--checkpointing_steps", "epoch",
            "--with_tracking",
            "--project_dir", str(tmp_path),
        )
        assert r.returncode == 0, r.stderr
        assert (tmp_path / "epoch_0").exists(), list(tmp_path.iterdir())
        r2 = _run_example(
            "complete_cv_example.py",
            "--project_dir", str(tmp_path),
            "--resume_from_checkpoint", str(tmp_path / "epoch_0"),
        )
        assert r2.returncode == 0, r2.stderr

    def test_inference_distributed_example(self):
        r = _run_inference_example(os.path.join("inference", "distributed.py"))
        assert r.returncode == 0, r.stderr
        assert "distributed generation done" in r.stdout

    def test_inference_distributed_seq2seq_example(self):
        r = _run_inference_example(os.path.join("inference", "distributed_seq2seq.py"))
        assert r.returncode == 0, r.stderr
        assert "generated" in r.stdout

    def test_inference_tensor_parallel_example(self):
        r = _run_inference_example(os.path.join("inference", "tensor_parallel.py"))
        assert r.returncode == 0, r.stderr
        assert "tensor-parallel generation" in r.stdout

    def test_inference_pippy_example(self):
        r = _run_inference_example(os.path.join("inference", "pippy.py"))
        assert r.returncode == 0, r.stderr
        assert "pipelined forward OK" in r.stdout

    @pytest.mark.parametrize(
        "script,marker",
        [
            ("bert.py", "encoder dispatch OK"),
            ("gpt2.py", "generation OK"),
            ("t5.py", "seq2seq dispatch + generation OK"),
            ("moe.py", "moe generation OK"),
        ],
    )
    def test_inference_architecture_matrix(self, script, marker):
        """Per-architecture dispatch/serving scripts (reference
        examples/inference/pippy/{bert,gpt2,t5}.py analog + MoE)."""
        r = _run_inference_example(os.path.join("inference", script))
        assert r.returncode == 0, r.stderr
        assert marker in r.stdout

    def test_complete_example_checkpoints_and_resumes(self, tmp_path):
        r = _run_example(
            "complete_nlp_example.py",
            "--checkpointing_steps", "epoch",
            "--with_tracking",
            "--project_dir", str(tmp_path),
        )
        assert r.returncode == 0, r.stderr
        assert (tmp_path / "epoch_0").exists(), list(tmp_path.iterdir())
        # resume from the epoch checkpoint: must start at epoch 1 == done
        r2 = _run_example(
            "complete_nlp_example.py",
            "--project_dir", str(tmp_path),
            "--resume_from_checkpoint", str(tmp_path / "epoch_0"),
        )
        assert r2.returncode == 0, r2.stderr


class TestCanonDiff:
    """The canon-diff machinery (reference test_utils/examples.py +
    tests/test_examples.py:290): every fenced by_feature script must be the
    canonical example plus `# New Code #` fenced additions, and must keep
    the bulk of the canon's training loop."""

    CANON = os.path.join(EXAMPLES, "nlp_example.py")
    FENCED = (
        "by_feature/early_stopping.py",
        "by_feature/profiler.py",
        "by_feature/multi_process_metrics.py",
        "by_feature/automatic_gradient_accumulation.py",
        "by_feature/schedule_free.py",
        "by_feature/cross_validation.py",
    )

    @pytest.mark.parametrize("rel", FENCED)
    def test_additions_are_fenced(self, rel):
        from accelerate_tpu.test_utils.examples import fence_violations

        bad = fence_violations(self.CANON, os.path.join(EXAMPLES, rel))
        assert not bad, (
            f"{rel}: lines added outside '# New Code #' fences:\n"
            + "\n".join(f"  {n}: {l}" for n, l in bad[:10])
        )

    @pytest.mark.parametrize("rel", FENCED)
    def test_canon_loop_survives(self, rel):
        from accelerate_tpu.test_utils.examples import canon_coverage

        cov = canon_coverage(self.CANON, os.path.join(EXAMPLES, rel))
        assert cov >= 0.55, f"{rel}: only {cov:.0%} of the canon remains — a rewrite, not a feature diff"


class TestExamplesDiff:
    """Feature scripts must build on the base example, not fork it."""

    def _src(self, rel):
        with open(os.path.join(EXAMPLES, rel)) as f:
            return f.read()

    def test_feature_scripts_reuse_base_data_pipeline(self):
        for rel in (
            "by_feature/gradient_accumulation.py",
            "by_feature/tracking.py",
            "by_feature/checkpointing.py",
            "by_feature/local_sgd.py",
            "by_feature/memory.py",
            "complete_nlp_example.py",
        ):
            src = self._src(rel)
            assert "from nlp_example import" in src, f"{rel} copies instead of importing"
            assert "class ParaphraseDataset" not in src, f"{rel} duplicates the dataset"

    def test_feature_scripts_keep_eval_contract(self):
        for rel in ("nlp_example.py", "by_feature/gradient_accumulation.py", "complete_nlp_example.py"):
            src = self._src(rel)
            assert "gather_for_metrics" in src, rel

    def test_gradient_accumulation_uses_accumulate_context(self):
        src = self._src("by_feature/gradient_accumulation.py")
        assert "accelerator.accumulate(" in src
        assert "% gradient_accumulation_steps" not in src, "manual gating defeats the feature"

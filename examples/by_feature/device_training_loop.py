"""by_feature/device_training_loop: the TPU performance path. One compiled call
runs `steps_per_call` FULL optimizer steps (`lax.scan` over stacked step-batches),
so the per-call host cost (argument processing, dispatch) is paid once per K
steps instead of every step. A fixed per-call tax is what held the bs-32 config
to 0.335 MFU in the builder-side sweep of bench_suite_r04.jsonl
(docs/concepts/performance.md); the device loop divides it by K, and
`bench.py` auto-selects K=10 for exactly this reason.

No reference counterpart: the reference's per-step backward/step choreography
cannot batch host dispatch; this exists because XLA lets the whole loop live on
device.
"""

import argparse
import os
import sys

import optax

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
from nlp_example import MAX_LEN, get_dataset  # noqa: E402

from accelerate_tpu import Accelerator, SimpleDataLoader
from accelerate_tpu.data_loader import BatchSampler, SeedableRandomSampler
from accelerate_tpu.models import bert_tiny, create_bert_model
from accelerate_tpu.utils import set_seed


def training_function(args):
    accelerator = Accelerator(mixed_precision=args.mixed_precision)
    set_seed(args.seed)
    config = bert_tiny()
    model = create_bert_model(config, seq_len=MAX_LEN)
    data = get_dataset(config.vocab_size - 1, n=args.train_size)

    # The loader collates steps_per_call step-batches as ONE [K*b, ...] array:
    # one host->device transfer, one dispatch, K optimizer steps on device.
    sampler = SeedableRandomSampler(num_samples=len(data), seed=args.seed)
    train_dl = SimpleDataLoader(
        data, BatchSampler(sampler, args.batch_size * args.steps_per_call, drop_last=True)
    )
    optimizer = optax.adamw(args.lr)
    model, optimizer, train_dl = accelerator.prepare(model, optimizer, train_dl)

    if len(train_dl) == 0:
        raise SystemExit(
            f"train_size={args.train_size} is smaller than one stacked call "
            f"(batch_size*steps_per_call = {args.batch_size * args.steps_per_call}); "
            "lower --steps_per_call/--batch_size or raise --train_size"
        )
    step_fn = accelerator.train_step(steps_per_call=args.steps_per_call)
    loss = None
    steps = 0
    for epoch in range(args.epochs):
        train_dl.set_epoch(epoch)
        for batch in train_dl:
            loss = step_fn(batch)  # K steps; returns the LAST step's loss
            steps += args.steps_per_call
    accelerator.print(
        f"device training loop: {steps} optimizer steps in {steps // args.steps_per_call} "
        f"dispatches (steps_per_call={args.steps_per_call}) final loss {float(loss):.4f}"
    )


if __name__ == "__main__":
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--mixed_precision", default="bf16", choices=["no", "bf16"])
    parser.add_argument("--batch_size", type=int, default=8)
    parser.add_argument(
        "--steps_per_call",
        type=int,
        default=4,
        help="full optimizer steps scanned per compiled call (bf16 only: dynamic "
        "fp16 loss scaling needs per-step host decisions and is rejected)",
    )
    parser.add_argument("--lr", type=float, default=1e-3)
    parser.add_argument("--epochs", type=int, default=2)
    parser.add_argument("--seed", type=int, default=42)
    parser.add_argument("--train_size", type=int, default=256)
    training_function(parser.parse_args())

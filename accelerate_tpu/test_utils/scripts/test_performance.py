"""Launched integration gate: accuracy floor + peak-memory ceiling per strategy.

Parity: the reference gates every strategy on launched end-to-end quality —
eval accuracy >= `--performance_lower_bound` (0.82 pattern,
`test_utils/scripts/external_deps/test_performance.py:199-202`,
`tests/fsdp/test_fsdp.py:214`) and peak memory <= an upper bound
(`external_deps/test_peak_memory_usage.py`, `tests/fsdp/test_fsdp.py:313-349`).

Two zero-egress tasks (no network — parity for the reference's MRPC download,
`test_utils/training.py:64`, `tests/test_samples/MRPC`):

- `text_pair` (default, reference-grade): paraphrase detection over the
  committed CSV fixture (`tests/test_samples/text_pair`). A from-scratch
  bert-tiny must learn a slot-wise synonym-matching circuit to clear 0.82 dev
  accuracy — a 10x-wrong LR never leaves the ln(2) saddle, a subtly broken
  grad path caps below the floor (the mutation audit in
  tests/test_integration_gates.py proves the floor binds).
- `token_parity` (fast tier): the label is the parity of the first token id,
  learnable in a few steps — checks the stack end-to-end, not training quality.

Run via `accelerate-tpu launch` (tests/test_integration_gates.py) or directly:

    python -m accelerate_tpu.test_utils.scripts.test_performance \
        --strategy full_shard --performance_lower_bound 0.82
"""

import argparse
import json
import os
import sys

import numpy as np


def make_dataset(n: int, seq_len: int, vocab: int, seed: int):
    rng = np.random.default_rng(seed)
    ids = rng.integers(2, vocab, size=(n, seq_len)).astype(np.int32)
    # The label-carrying first token is drawn from a small id set shared by train
    # and eval, so the gate tests that training WORKS (the pooler reads position 0),
    # not whether embeddings of never-seen ids generalize.
    ids[:, 0] = rng.integers(2, 18, size=(n,))
    labels = (ids[:, 0] % 2).astype(np.int64)
    return [{"input_ids": ids[i], "labels": labels[i]} for i in range(n)]


def find_text_pair_dir() -> str:
    """Locate the committed fixture: explicit flag/env first, then the source
    checkout layout relative to this file."""
    env = os.environ.get("ACCELERATE_TPU_TEST_SAMPLES")
    if env:
        return env
    here = os.path.dirname(os.path.abspath(__file__))
    repo = os.path.dirname(os.path.dirname(os.path.dirname(here)))
    cand = os.path.join(repo, "tests", "test_samples", "text_pair")
    if os.path.isdir(cand):
        return cand
    raise FileNotFoundError(
        "text_pair fixture not found; pass --data_dir or set ACCELERATE_TPU_TEST_SAMPLES"
    )


def load_text_pair(data_dir: str, split: str, seq_len: int = 16):
    """CSV rows -> {input_ids, token_type_ids, labels} dicts ([CLS] a [SEP] b [SEP])."""
    import csv

    with open(os.path.join(data_dir, "vocab.txt")) as f:
        vocab = {w.strip(): i for i, w in enumerate(f)}
    cls_id, sep_id = vocab["[CLS]"], vocab["[SEP]"]
    rows = []
    with open(os.path.join(data_dir, f"{split}.csv"), newline="") as f:
        for r in csv.DictReader(f):
            a = [vocab[w] for w in r["sentence1"].split()]
            b = [vocab[w] for w in r["sentence2"].split()]
            toks = [cls_id, *a, sep_id, *b, sep_id]
            ids = np.zeros(seq_len, np.int32)
            types = np.zeros(seq_len, np.int32)
            ids[: len(toks)] = toks
            types[len(a) + 2 : len(toks)] = 1
            rows.append(
                {"input_ids": ids, "token_type_ids": types, "labels": np.int64(int(r["label"]))}
            )
    return rows


def build_accelerator(strategy: str, mixed_precision: str):
    from accelerate_tpu import Accelerator
    from accelerate_tpu.utils import FullyShardedDataParallelPlugin

    if strategy == "dp":
        return Accelerator(mixed_precision=mixed_precision)
    plugin_kwargs = {
        "full_shard": dict(sharding_strategy="FULL_SHARD"),
        "shard_grad_op": dict(sharding_strategy="SHARD_GRAD_OP"),
        "offload": dict(sharding_strategy="FULL_SHARD", offload_optimizer_state=True),
    }[strategy]
    return Accelerator(
        mixed_precision=mixed_precision,
        fsdp_plugin=FullyShardedDataParallelPlugin(min_num_params=1024, **plugin_kwargs),
    )


def peak_memory_mb() -> float | None:
    """Per-device peak bytes from the backend, if it reports them (TPU does; the
    host-CPU test platform usually doesn't)."""
    import jax

    stats = jax.local_devices()[0].memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    return peak / (1024 * 1024) if peak else None


def main(argv=None):
    parser = argparse.ArgumentParser()
    parser.add_argument("--strategy", default="dp", choices=["dp", "full_shard", "shard_grad_op", "offload"])
    parser.add_argument("--task", default="text_pair", choices=["text_pair", "token_parity"])
    parser.add_argument("--performance_lower_bound", type=float, default=0.82)
    parser.add_argument("--peak_memory_upper_bound_mb", type=float, default=None)
    parser.add_argument("--mixed_precision", default="bf16")
    parser.add_argument("--epochs", type=int, default=None, help="default: 14 text_pair, 10 token_parity")
    parser.add_argument("--lr", type=float, default=None, help="default: 3e-4 text_pair, 1e-3 token_parity")
    parser.add_argument("--batch_size", type=int, default=32, help="global batch size")
    parser.add_argument("--seq_len", type=int, default=None)
    parser.add_argument("--data_dir", default=None, help="text_pair fixture dir (default: auto-discover)")
    parser.add_argument("--train_size", type=int, default=256, help="token_parity only")
    parser.add_argument("--eval_size", type=int, default=96, help="token_parity only")
    args = parser.parse_args(argv)

    import jax
    import optax

    from accelerate_tpu import SimpleDataLoader
    from accelerate_tpu.data_loader import BatchSampler, SeedableRandomSampler
    from accelerate_tpu.models import bert_tiny, create_bert_model
    from accelerate_tpu.utils.random import set_seed

    set_seed(42)
    accelerator = build_accelerator(args.strategy, args.mixed_precision)

    cfg = bert_tiny()
    if args.task == "text_pair":
        # Calibrated recipe (found empirically on the CPU test tier): from-scratch bert-tiny crosses
        # dev 0.87 at epoch 8 and ~0.93 at 11 with adamw(3e-4, wd 0.01), global
        # batch 32, seeded reshuffle; 14 epochs leaves margin over the 0.82 floor.
        args.seq_len = args.seq_len or 16
        args.epochs = args.epochs or 14
        args.lr = args.lr or 3e-4
        data_dir = args.data_dir or find_text_pair_dir()
        train_data = load_text_pair(data_dir, "train", args.seq_len)
        eval_data = load_text_pair(data_dir, "dev", args.seq_len)
        tx = optax.adamw(args.lr, weight_decay=0.01)
        # Seeded reshuffle each epoch (DataLoaderShard advances the sampler epoch).
        train_sampler = SeedableRandomSampler(train_data, seed=7)
    else:
        args.seq_len = args.seq_len or 32
        args.epochs = args.epochs or 10
        args.lr = args.lr or 1e-3
        train_data = make_dataset(args.train_size, args.seq_len, cfg.vocab_size, seed=0)
        # Deliberately NOT a multiple of the batch size: the last eval batch is
        # padded by the loader and gather_for_metrics must truncate the duplicates.
        eval_data = make_dataset(args.eval_size - 5, args.seq_len, cfg.vocab_size, seed=1)
        tx = optax.adamw(args.lr)
        train_sampler = range(len(train_data))

    model = create_bert_model(cfg, seq_len=args.seq_len)
    train_dl = SimpleDataLoader(train_data, BatchSampler(train_sampler, args.batch_size, drop_last=True))
    eval_dl = SimpleDataLoader(eval_data, BatchSampler(range(len(eval_data)), args.batch_size, drop_last=False))

    pmodel, popt, ptrain_dl, peval_dl = accelerator.prepare(model, tx, train_dl, eval_dl)

    step_fn = accelerator.train_step()
    loss = None
    for _ in range(args.epochs):
        for batch in ptrain_dl:
            loss = step_fn(batch)
    final_loss = float(loss)

    hits = []
    for batch in peval_dl:
        logits = pmodel.eval_apply(batch["input_ids"], token_type_ids=batch.get("token_type_ids"))
        pred = logits.argmax(-1)
        pred, labels = accelerator.gather_for_metrics((pred, batch["labels"]))
        hits.append(np.asarray(pred) == np.asarray(labels))
    hits = np.concatenate(hits)
    assert hits.shape[0] == len(eval_data), (
        f"gather_for_metrics returned {hits.shape[0]} samples, expected {len(eval_data)} "
        f"(padding not truncated)"
    )
    accuracy = float(hits.mean())

    peak_mb = peak_memory_mb()
    result = {
        "strategy": args.strategy,
        "task": args.task,
        "accuracy": accuracy,
        "final_loss": final_loss,
        "peak_memory_mb": peak_mb,
        "n_devices": jax.device_count(),
    }
    accelerator.print(json.dumps(result))

    assert accuracy >= args.performance_lower_bound, (
        f"accuracy gate FAILED for {args.strategy}: {accuracy:.4f} < {args.performance_lower_bound}"
    )
    if args.peak_memory_upper_bound_mb is not None and peak_mb is not None:
        assert peak_mb <= args.peak_memory_upper_bound_mb, (
            f"peak-memory gate FAILED for {args.strategy}: {peak_mb:.1f}MB > "
            f"{args.peak_memory_upper_bound_mb}MB"
        )
    accelerator.print(f"Performance gate passed: {args.strategy} accuracy={accuracy:.4f}")
    return result


if __name__ == "__main__":
    main()
    sys.exit(0)

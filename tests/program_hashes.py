"""sha256 of the lowered programs of the tiny families: the forward, a train
step, and the engine's decode chunk and insert. A change that claims "no
program moved" is held to `program_hashes.json` by `test_program_hashes.py`.

As a script it prints the table, and with `--write` records it in the golden
file under this installation's `jax.__version__`:

    JAX_PLATFORMS=cpu python tests/program_hashes.py [--write] [family ...]

Regenerate the file FROM THE PARENT COMMIT of a change that is meant to move no
program, and from the change itself only where a program is meant to move.
"""

from __future__ import annotations

import hashlib
import json
import os
import sys

if __name__ == "__main__":
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

GOLDEN = os.path.join(os.path.dirname(os.path.abspath(__file__)), "program_hashes.json")

#: family -> (the `models` factory, its tiny preset)
FAMILIES = {
    "gpt_neox": ("create_gpt_neox_model", "gpt_neox_tiny"),
    "llama": ("create_llama_model", "llama_tiny"),
    "latent_moe": ("create_latent_moe_model", "latent_moe_tiny"),
    "latent_moe_hc": ("create_latent_moe_model", "latent_moe_hc_tiny"),
    "olmo_hybrid": ("create_olmo_hybrid_model", "olmo_hybrid_tiny"),
    "falcon_h1": ("create_falcon_h1_model", "falcon_h1_tiny"),
}

#: The engines beside the plain one, for the two families that serve them all.
ENGINE_VARIANTS = {
    "speculative": {"speculative": True},
    "penalty_sampling": {"use_repetition_penalty": True, "do_sample": True, "top_k": 4, "top_p": 0.9},
    "kv_int8": {"kv_cache_dtype": "int8"},
}
FAMILIES_WITH_VARIANTS = ("gpt_neox", "llama")

INSERT_BUCKET = 32


def _digest(lowered) -> str:
    return hashlib.sha256(lowered.as_text().encode()).hexdigest()[:16]


def engine_hashes(model, **engine) -> dict:
    """`{"chunk": ..., "insert": ...}` of one tiny engine's two programs."""
    from accelerate_tpu.generation import _operand
    from accelerate_tpu.serving import ContinuousBatcher

    batcher = ContinuousBatcher(model, num_slots=2, max_length=64, chunk_size=4, **engine)
    chunk = batcher.lower_decode_chunk()
    insert = batcher._insert_fn(INSERT_BUCKET).lower(
        batcher.params, batcher._cache, batcher._presence, jnp.zeros((1, INSERT_BUCKET), jnp.int32),
        _operand(1, np.int32), _operand(0, np.int32), _operand(0, np.int32),
        jnp.asarray(np.zeros((batcher.pages_per_slot,), np.int32)),
        _operand(0, np.int32), _operand(1.0, np.float32), _operand(1.0, np.float32),
        batcher._rng, batcher._new_first_token(),
    )
    return {"chunk": _digest(chunk), "insert": _digest(insert)}


def family_hashes(family: str) -> dict:
    """`{program name: first 16 hex digits of its lowered text's sha256}`."""
    from accelerate_tpu import models
    from accelerate_tpu.models.llama import causal_lm_loss

    create, tiny = (getattr(models, name) for name in FAMILIES[family])
    model = create(tiny(), seq_len=32)
    ids = jnp.zeros((2, 16), jnp.int32)
    out = {
        "forward": _digest(jax.jit(lambda p, i: model.module.apply(p, i)).lower(model.params, ids)),
        "train": _digest(
            jax.jit(jax.grad(lambda p, b: causal_lm_loss(p, b, model.module.apply))).lower(
                model.params, {"input_ids": ids})),
    }
    out.update(engine_hashes(model))
    if family in FAMILIES_WITH_VARIANTS:
        for variant, engine in ENGINE_VARIANTS.items():
            out.update({f"{variant}.{name}": digest for name, digest in engine_hashes(model, **engine).items()})
    return out


def load_golden() -> dict:
    with open(GOLDEN) as f:
        return json.load(f)


def main(argv) -> int:
    write = "--write" in argv
    families = [a for a in argv if not a.startswith("--")] or list(FAMILIES)
    table = {}
    for family in families:
        table[family] = family_hashes(family)
        print(family, " ".join(f"{k}={v}" for k, v in table[family].items()), flush=True)
    if write:
        golden = load_golden() if os.path.exists(GOLDEN) else {}
        golden.setdefault(jax.__version__, {}).update(table)
        with open(GOLDEN, "w") as f:
            json.dump(golden, f, indent=1, sort_keys=True)
            f.write("\n")
        print(f"wrote {GOLDEN} [{jax.__version__}]")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))

"""TPU115 flag fixture: a serving engine whose KV read is pinned to the XLA
live-page read by a literal attention_impl="xla". (The interpret=True kernel-call variant is unit-tested in
test_analysis_rules.test_tpu115_interpret_variant; the tree-walk contract
allows exactly one finding per flag fixture.)"""

import jax.numpy as jnp

from accelerate_tpu.serving import ContinuousBatcher


def build_engine(model):
    # FLAG: the literal pins the read where the engine is built.
    return ContinuousBatcher(model, max_queue=8, attention_impl="xla")

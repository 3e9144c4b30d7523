"""TPU115 clean fixture: the sanctioned spellings — the kernel path named,
impl flags threaded as variables, and kernels left to auto-select interpret
mode."""

import jax.numpy as jnp

from accelerate_tpu.ops.paged_attention import paged_decode_attention
from accelerate_tpu.serving import ContinuousBatcher


def build_engine(model):
    # The kernel path: the page-table gather fused into the attention walk.
    return ContinuousBatcher(model, max_queue=8, attention_impl="pallas_paged")


def build_ab_engine(model, impl):
    # A/B harnesses thread the impl as a variable; the linter only flags the
    # literal "xla" pin.
    return ContinuousBatcher(model, max_queue=8, attention_impl=impl)


def attend(q, k_pool, v_pool, table, pos):
    # interpret=None (the default) compiles on TPU and interprets off it.
    return paged_decode_attention(q, k_pool, v_pool, table, pos)

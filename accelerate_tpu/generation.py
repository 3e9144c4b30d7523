"""KV-cached autoregressive generation — the serving path behind the reference's
big-model-inference benchmark (benchmarks/big_model_inference.py: model load time +
per-token generation latency are the published numbers, benchmarks/README.md:27-37).

TPU design: one compiled prefill (writes the whole prompt into the KV cache and
returns first-token logits — the TTFT program) plus ONE compiled decode LOOP
(`lax.while_loop` carrying the cache, token, rng, and finished mask) that runs
sampling, EOS masking, and early exit entirely on device. A per-token Python loop
would pay a host round-trip per token. The loop's token-count bound is a traced scalar inside a
power-of-two-bucketed buffer, so prompt-length changes don't recompile it. The
cache lives in the flax "cache" collection (models/llama.py LlamaAttention decode
path) with static capacity `max_length`.
"""

from __future__ import annotations

import dataclasses
import threading
from dataclasses import dataclass
from typing import Optional

import numpy as np

import jax
import jax.numpy as jnp


@dataclass
class GenerationConfig:
    max_new_tokens: int = 32
    do_sample: bool = False
    temperature: float = 1.0
    top_k: int = 0  # 0 = full vocab
    top_p: float = 1.0  # nucleus sampling; 1.0 = disabled (applied after top_k, HF order)
    repetition_penalty: float = 1.0  # HF CTRL-style: seen tokens' logits /p (if >0) else *p
    eos_token_id: Optional[int] = None
    pad_token_id: Optional[int] = None  # fill for finished rows; defaults to eos
    # Self-speculative decode (speculative.py): > 0 turns each fused-loop
    # iteration into an n-gram draft + one (draft_tokens+1)-position verify
    # dispatch that emits every greedily-confirmed draft plus one bonus token.
    # Greedy-only (do_sample / repetition_penalty raise) and token-identical
    # to draft_tokens=0 by construction; both knobs shape the compiled loop.
    draft_tokens: int = 0
    draft_ngram: int = 2


def _sample(logits, config: GenerationConfig, rng, temperature=None):
    """[B, V] logits -> [B] token ids. `temperature` may be a traced scalar (the
    fused decode loop passes it as an operand so changing it never recompiles)."""
    if not config.do_sample:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32), rng
    if temperature is None:
        temperature = config.temperature
    logits = logits.astype(jnp.float32) / jnp.maximum(temperature, 1e-6)
    if config.top_k:
        kth = jax.lax.top_k(logits, config.top_k)[0][:, -1:]
        logits = jnp.where(logits < kth, -1e30, logits)
    if config.top_p < 1.0:
        # Nucleus: keep the smallest prefix of the descending-prob ordering
        # whose mass reaches top_p (the top token always survives: its
        # EXCLUSIVE cumulative mass is 0 < top_p). Sort/cumsum/threshold is
        # jit-static — no shapes depend on the data.
        sorted_logits = jnp.flip(jnp.sort(logits, axis=-1), axis=-1)
        probs = jax.nn.softmax(sorted_logits, axis=-1)
        exclusive_cum = jnp.cumsum(probs, axis=-1) - probs
        keep = exclusive_cum < config.top_p
        # min_tokens_to_keep=1 (HF semantics): top_p <= 0 would otherwise mask
        # EVERYTHING and categorical over all -1e30 samples uniform gibberish.
        keep = keep.at[..., 0].set(True)
        kth = jnp.min(jnp.where(keep, sorted_logits, jnp.inf), axis=-1, keepdims=True)
        logits = jnp.where(logits < kth, -1e30, logits)
    rng, sub = jax.random.split(rng)
    return jax.random.categorical(sub, logits, axis=-1).astype(jnp.int32), rng


def _apply_repetition_penalty(logits, presence, penalty: float):
    """HF RepetitionPenaltyLogitsProcessor (CTRL) semantics: every token marked
    in `presence` [B, V] gets its logit divided by the penalty when positive,
    multiplied when negative — both push re-use down for penalty > 1."""
    scores = logits.astype(jnp.float32)
    penalized = jnp.where(scores > 0, scores / penalty, scores * penalty)
    return jnp.where(presence, penalized, scores)


def _trim_at_eos(generated, eos_token_id, max_new: int):
    """HF generate's output contract: the fused loop emits a fixed [B, max_new]
    buffer (pad after EOS); return only up to the step where every row had
    finished. One host read of the small token matrix."""
    if eos_token_id is None:
        return generated
    toks = np.asarray(generated)
    all_finished = ((toks == eos_token_id).cumsum(axis=1) > 0).all(axis=0)
    idx = np.argmax(all_finished) if all_finished.any() else max_new - 1
    return generated[:, : idx + 1]


def _bucket_for(max_new: int) -> int:
    return 1 << (max_new - 1).bit_length()  # next power of two >= max_new


def _rewind_cache_index(cache, delta):
    """Roll back every attention module's `cache_index` by `delta` — the
    speculative accept/reject step: a verify block wrote draft_tokens+1 K/V
    rows and advanced the shared index past them, but only the accepted prefix
    may count. The rejected tail stays physically in the cache; it is
    unreachable (`update_decode_cache` masks `cols < cache_index + s`, and the
    next block's writes start AT the rewound index, covering the stale region
    before any query can see it). `delta` may be a traced scalar."""
    def fix(path, leaf):
        key = getattr(path[-1], "key", None) if path else None
        return leaf - delta if key == "cache_index" else leaf

    return jax.tree_util.tree_map_with_path(fix, cache)


def _operand(value, dtype):
    """Explicit host-to-device push of a scalar/array operand. The numpy hop
    matters: `jnp.asarray(python_scalar)` (and eager jnp ops on Python
    constants) are IMPLICIT transfers that an armed `jax.transfer_guard`
    ("disallow") rejects, while `jnp.asarray(np.ndarray)` is explicit — the
    sanctioned step-boundary pattern, with a strong dtype so jit signatures
    never drift."""
    return jnp.asarray(np.asarray(value, dtype))


_DEFAULT_RNG = None


def _default_rng():
    """The rng=None default key, built once and reused: `jax.random.key(0)`
    per call is an implicit host-to-device push that (a) costs a transfer per
    generate() and (b) trips an armed TraceGuard. Key values are immutable
    (consumers split, never mutate), so sharing is semantically identical to a
    fresh key(0) each call. Built lazily — never at import time (TPU109)."""
    global _DEFAULT_RNG
    if _DEFAULT_RNG is None:
        _DEFAULT_RNG = jax.random.key(0)
    return _DEFAULT_RNG


def _params_resolver(model):
    """params -> params preprocessing for the compiled programs. Quantized bundles
    (load_and_quantize_model) carry QuantTensor leaves that the raw flax module
    can't consume; dequantize INSIDE the program so XLA keeps the int8/packed
    buffers in HBM and fuses `scale * q` into each consumer — serving stays at the
    quantized footprint (the reference's bnb int8 inference path)."""
    from .utils.quantization import dequantize_params, is_quant_entry

    leaves = jax.tree_util.tree_leaves(model.params, is_leaf=is_quant_entry)
    if not any(is_quant_entry(l) for l in leaves):
        return lambda p: p
    qc = getattr(model, "quantization_config", None)
    compute_dtype = getattr(qc, "compute_dtype", None) or jnp.bfloat16
    return lambda p: dequantize_params(p, compute_dtype)


def make_causal_programs(
    module,
    resolve,
    step_mask_operand: bool = False,
    verify_block: bool = False,
):
    """(prefill, step[, verify]) raw callables for a decode-cache causal-LM
    module — the factored seam that `Generator` jits directly and
    `serving.ContinuousBatcher` composes into its slot-insert / chunked-decode
    programs.

    `prefill(params, input_ids, positions, attention_mask=None)` writes the whole
    prompt into a fresh cache and returns `(last_logits, cache)`;
    `step(params, cache, token, position)` advances one token. Both are un-jitted
    so callers can trace them inside larger fused programs.

    `step_mask_operand=True` gives `step` a fifth argument threaded through as
    the module's `attention_mask`: the slot cache reads it as the
    [B, pages_per_slot] int32 page table (a traced operand — the one decode
    executable survives every admission), since slot decode never carries a
    boolean mask of its own. The module config's `decode_attention_impl`
    decides what the step/verify programs DO with that table: "xla" gathers
    the pages into a logical buffer (parity oracle), "pallas_paged" hands the
    table to the fused `ops/paged_attention` kernels — either way the program
    signatures here are identical, so serving's compiled-once discipline and
    the traced-operand page tables are implementation-agnostic.

    Weight-only quantization rides the module config's `weight_dtype`
    ("bf16" default): "int8" wraps every apply below in
    `ops.quantization.weight_autocast`, so Dense kernels stored as
    per-output-channel int8 entries (`quantize_params_int8` — the serving
    engine's params setter) compute through the fused int8-epilogue matmul.
    The wrap is trace-time only (the interceptor rewrites the bound method
    during tracing); "bf16" is a no-op context.

    `verify_block=True` appends the speculative-decode seam to the tuple:
    `verify(params, cache, tokens, positions, mask=None)` scores a [B, s] token
    BLOCK (the pending token plus s-1 draft proposals) in ONE dispatch,
    writing every block position's K/V and returning the full [B, s, V]
    logits plus the mutated cache — the multi-token twin of `step`; the
    serving engine passes its page table as `mask`, the static `Generator`
    (a dense decode cache) leaves it out. Position j's logits are computed after
    exactly the block prefix <= j (the cache paths mask per-query), so
    `argmax(logits[:, j])` is precisely the token greedy decode would emit
    after accepting the first j block tokens — the property the accept loop
    relies on for token-identical output."""

    from .ops.quantization import weight_autocast

    weight_dtype = getattr(getattr(module, "config", None), "weight_dtype", "bf16")

    def prefill(params, input_ids, positions, attention_mask=None):
        # attention_mask (left-padded batch prompts): rides into the cached
        # attention as the persistent pad mask (update_decode_cache).
        # The head runs on the last column alone (`models.llama.rows_for_head`):
        # the one row whose logits are returned.
        batch, length = input_ids.shape
        with weight_autocast(weight_dtype):
            logits, mutated = module.apply(
                resolve(params), input_ids, attention_mask, positions, mutable=["cache"],
                logits_at=jnp.full((batch,), length - 1, jnp.int32),
            )
        return logits[:, 0, :], mutated["cache"]

    def step(params, cache, token, position):
        with weight_autocast(weight_dtype):
            logits, mutated = module.apply(
                {**resolve(params), "cache": cache},
                token[:, None],
                None,
                position[:, None],
                mutable=["cache"],
            )
        return logits[:, -1, :], mutated["cache"]

    def step_with_mask(params, cache, token, position, mask):
        with weight_autocast(weight_dtype):
            logits, mutated = module.apply(
                {**resolve(params), "cache": cache},
                token[:, None],
                mask,
                position[:, None],
                mutable=["cache"],
            )
        return logits[:, -1, :], mutated["cache"]

    def verify(params, cache, tokens, positions, mask=None):
        with weight_autocast(weight_dtype):
            logits, mutated = module.apply(
                {**resolve(params), "cache": cache}, tokens, mask, positions, mutable=["cache"]
            )
        return logits, mutated["cache"]

    step_fn = step_with_mask if step_mask_operand else step
    if verify_block:
        return prefill, step_fn, verify
    return prefill, step_fn


def make_cached_prefill_program(module, resolve):
    """`prefill_with_cache(params, cache, input_ids, positions, attention_mask,
    logits_at)` — prefill a token block INTO AN EXISTING dense decode cache,
    continuing at the cache's own `cache_index` instead of position 0, and return
    `[B, 1, V]` logits plus the mutated cache: the rows `logits_at` (`[B]` int32
    into `S`) names, the only ones the module applies its final norm and head to
    (`models.llama.rows_for_head`). The paged serving engine's shared-prefix
    insert drives this: the prefix pages are gathered into a batch-1 dense cache
    (`cache_index` = matched length), only the unmatched SUFFIX runs through the
    model here — the prefill FLOPs a shared system prompt would have cost are
    simply never issued — and the result is scattered back into pool pages.
    `attention_mask` ([B, S], 1 = real; else None) is for a family whose layers run a
    recurrence over the block: a bucket's padding must leave its state alone."""

    from .ops.quantization import weight_autocast

    weight_dtype = getattr(getattr(module, "config", None), "weight_dtype", "bf16")

    def prefill_with_cache(params, cache, input_ids, positions, attention_mask, logits_at):
        with weight_autocast(weight_dtype):
            logits, mutated = module.apply(
                {**resolve(params), "cache": cache},
                input_ids,
                attention_mask,
                positions,
                mutable=["cache"],
                logits_at=logits_at,
            )
        return logits, mutated["cache"]

    return prefill_with_cache


class Generator:
    """Compiled prefill + decode-step pair for a causal-LM Model bundle.

    Reusable across prompts of the same (batch, prompt_len) shape; per-token decode is
    shape-stable for any prompt length up to the cache capacity.
    """

    def __init__(self, model, max_new_tokens: int = 32, max_length: Optional[int] = None):
        if getattr(model, "module", None) is None or not hasattr(model.module, "config"):
            raise ValueError("generate() needs a Model bundle built from an in-tree flax module")
        self.base_config = model.module.config
        self.params = model.params
        self.max_new_tokens = max_new_tokens
        self.max_length = max_length or self.base_config.max_position_embeddings
        decode_cfg = dataclasses.replace(self.base_config, decode_cache_length=self.max_length)
        self.decode_module = type(model.module)(decode_cfg)

        prefill, step, verify = make_causal_programs(
            self.decode_module, _params_resolver(model), verify_block=True
        )
        self._prefill = jax.jit(prefill)
        self._step_inner = step  # un-jitted: traced inside the fused decode loop
        self._verify_inner = verify  # un-jitted: traced inside the speculative loop
        self._decode_cache = {}

    def _decode_fn(self, bucket: int, config: GenerationConfig):
        """ONE compiled program for the whole decode loop (lax.while_loop): sampling,
        EOS masking, and early exit all happen on device. A Python token loop would
        pay one host round-trip per token, serializing decode at host latency
        instead of step latency.

        `bucket` (power of two) sizes the output buffer; the actual token bound is a
        TRACED scalar, so varying prompt lengths / max_new_tokens reuse one
        executable per bucket instead of recompiling the whole model."""
        # Only WHETHER a penalty applies shapes the program (the presence carry);
        # the penalty VALUE rides as a traced operand like temperature, so
        # sweeping it never recompiles the fused loop.
        # draft_ngram is inert without draft_tokens: normalize it out of the
        # key so a draft_tokens=0 control run never recompiles an identical
        # plain loop per ngram value.
        key = (bucket, config.do_sample, config.eos_token_id, config.pad_token_id,
               config.repetition_penalty != 1.0, config.draft_tokens,
               config.draft_ngram if config.draft_tokens else 0)
        if config.draft_tokens:
            if key not in self._decode_cache:
                self._decode_cache[key] = self._speculative_decode_fn(bucket, config)
            return self._decode_cache[key]
        if config.do_sample:
            # top_k and top_p shape the program (lax.top_k / the nucleus
            # threshold are trace-time); temperature rides in as a traced
            # operand so it never forces a recompile. Omitting a program-shaping
            # field here silently serves a STALE sampler compiled for another
            # config — exactly what happened when top_p first landed.
            key += (config.top_k, config.top_p)
        if key in self._decode_cache:
            return self._decode_cache[key]

        eos = config.eos_token_id
        pad_id = config.pad_token_id if config.pad_token_id is not None else (eos if eos is not None else 0)
        step_inner = self._step_inner
        use_penalty = config.repetition_penalty != 1.0

        def decode(params, cache, first_logits, next_positions, limit, temperature, penalty, rng, presence, *extra):
            # `next_positions`: the LOGICAL position of the first generated token —
            # a scalar (uniform prompts; Seq2Seq passes 1) or a per-row [B] vector
            # (left-padded ragged prompts: row with r real tokens continues at r).
            # `presence`: [B, V] bool of already-seen tokens when the config sets a
            # repetition penalty (the caller seeds it from the prompt; each
            # generated token joins it on device), else None.
            # `extra` operands (e.g. the encoder output for seq2seq models) thread
            # through unchanged to every step_inner call.
            b = first_logits.shape[0]

            def pick(logits, presence, rng):
                if use_penalty:
                    logits = _apply_repetition_penalty(logits, presence, penalty)
                token, rng = _sample(logits, config, rng, temperature)
                if use_penalty:
                    presence = presence.at[jnp.arange(b), token].set(True)
                return token, presence, rng

            token, presence, rng = pick(first_logits, presence, rng)
            tokens = jnp.full((b, bucket), jnp.int32(pad_id))
            tokens = tokens.at[:, 0].set(token)
            finished = jnp.zeros((b,), bool)

            def cond(carry):
                i, tokens, cache, token, rng, finished, presence = carry
                more = i < limit
                if eos is not None:
                    more &= ~jnp.all(finished | (token == eos))
                return more

            def body(carry):
                i, tokens, cache, token, rng, finished, presence = carry
                if eos is not None:
                    finished = finished | (token == eos)
                position = jnp.broadcast_to(next_positions + i - 1, (b,)).astype(jnp.int32)
                logits, cache = step_inner(params, cache, token, position, *extra)
                token, presence, rng = pick(logits, presence, rng)
                if eos is not None:
                    # Rows past their EOS emit pad/eos, matching HF generate's padding.
                    token = jnp.where(finished, jnp.int32(pad_id), token)
                tokens = tokens.at[:, i].set(token)
                return (i + 1, tokens, cache, token, rng, finished, presence)

            carry = (jnp.int32(1), tokens, cache, token, rng, finished, presence)
            _, tokens, cache, _, _, _, _ = jax.lax.while_loop(cond, body, carry)
            return tokens, cache

        fn = jax.jit(decode, donate_argnums=(1,))
        self._decode_cache[key] = fn
        return fn

    def _speculative_decode_fn(self, bucket: int, config: GenerationConfig):
        """The fused decode loop's draft-then-verify variant: each
        `lax.while_loop` iteration proposes `config.draft_tokens` continuations
        with the on-device n-gram drafter (`speculative.propose_ngram_drafts`
        over a history buffer riding the carry), scores the pending token plus
        all drafts in ONE (draft_tokens+1)-position verify dispatch, and emits
        the longest greedily-confirmed draft prefix plus one bonus token — so
        an iteration emits 1..draft_tokens+1 tokens for the latency of one
        dispatch, and greedy output stays token-identical to the plain loop
        (every emitted token is the model's own argmax given exactly the
        accepted prefix).

        Batch rows advance in LOCKSTEP (the dense cache's `cache_index` is
        shared): the accepted length is the minimum across unfinished rows, so
        a batch-1 call gets the full speedup and larger batches degrade toward
        plain decode, never past it. Rows that finish early emit pads, exactly
        like the plain loop. The rejected K/V tail is rolled back by rewinding
        `cache_index` (`_rewind_cache_index`); the token/history buffers carry
        `bucket + draft_tokens` columns of slack so the last block's masked
        window writes stay in bounds."""
        from .speculative import greedy_accept_length, propose_ngram_drafts

        if config.do_sample:
            raise ValueError(
                "speculative decoding is greedy-only: draft verification accepts "
                "argmax matches, which is not distribution-preserving under "
                "sampling — set do_sample=False or draft_tokens=0"
            )
        if config.repetition_penalty != 1.0:
            raise ValueError(
                "speculative decoding does not compose with repetition_penalty "
                "(the presence update is order-dependent across a verified "
                "block); set repetition_penalty=1.0 or draft_tokens=0"
            )
        eos = config.eos_token_id
        pad_id = config.pad_token_id if config.pad_token_id is not None else (eos if eos is not None else 0)
        verify_inner = self._verify_inner
        k_draft, m_gram = config.draft_tokens, config.draft_ngram

        def decode(params, cache, first_logits, next_positions, limit, history, hist_base, *extra):
            # `history` [B, max_length + k] int32: the observed context in
            # PHYSICAL order (prompt buffer, then generated tokens), seeded
            # with the prompt by the caller; `hist_base` = prompt buffer width.
            b = first_logits.shape[0]
            token = jnp.argmax(first_logits, axis=-1).astype(jnp.int32)
            width = bucket + k_draft
            tokens = jnp.full((b, width), jnp.int32(pad_id))
            tokens = tokens.at[:, 0].set(token)
            history = history.at[jnp.arange(b), hist_base].set(token)
            finished = (token == eos) if eos is not None else jnp.zeros((b,), bool)
            js = jnp.arange(k_draft + 1, dtype=jnp.int32)

            def cond(carry):
                i, tokens, cache, token, finished, history = carry
                more = i < limit
                if eos is not None:
                    more &= ~jnp.all(finished)
                return more

            def body(carry):
                i, tokens, cache, token, finished, history = carry
                hist_len = hist_base + i
                drafts, valid_len = propose_ngram_drafts(history, hist_len, k_draft, m_gram)
                block = jnp.concatenate([token[:, None], drafts], axis=1)
                base = jnp.broadcast_to(next_positions + i - 1, (b,)).astype(jnp.int32)
                positions = base[:, None] + js[None, :]
                logits, cache = verify_inner(params, cache, block, positions, *extra)
                greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)  # [B, k+1]
                accept = greedy_accept_length(drafts, greedy[:, :k_draft], valid_len)
                if eos is not None:
                    # Finished rows emit pads regardless; don't let them drag
                    # the lockstep minimum below the live rows' acceptance.
                    accept = jnp.where(finished, k_draft, accept)
                a_min = jnp.minimum(jnp.min(accept), limit - i - 1)  # scalar
                emit = js <= a_min
                if eos is not None:
                    cols, fin = [], finished
                    for j in range(k_draft + 1):
                        e = jnp.where(fin, jnp.int32(pad_id), greedy[:, j])
                        cols.append(e)
                        fin = fin | ((e == eos) & emit[j])
                    emitted = jnp.stack(cols, axis=1)
                    finished = fin
                else:
                    emitted = greedy
                # Masked window writes: positions past a_min keep their old
                # buffer contents (the next iteration starts there).
                window = jax.lax.dynamic_slice(tokens, (jnp.int32(0), i), (b, k_draft + 1))
                tokens = jax.lax.dynamic_update_slice(
                    tokens, jnp.where(emit[None, :], emitted, window), (jnp.int32(0), i)
                )
                hwin = jax.lax.dynamic_slice(history, (jnp.int32(0), hist_len), (b, k_draft + 1))
                history = jax.lax.dynamic_update_slice(
                    history, jnp.where(emit[None, :], emitted, hwin), (jnp.int32(0), hist_len)
                )
                token = jax.lax.dynamic_slice_in_dim(emitted, a_min, 1, axis=1)[:, 0]
                # Count only the accepted prefix: rewind the shared cache index
                # past the k - a_min rejected draft rows this dispatch wrote.
                cache = _rewind_cache_index(cache, k_draft - a_min)
                return (i + a_min + 1, tokens, cache, token, finished, history)

            carry = (jnp.int32(1), tokens, cache, token, finished, history)
            _, tokens, cache, _, _, _ = jax.lax.while_loop(cond, body, carry)
            return tokens, cache

        # Donate only the cache: the history buffer has no same-shaped output
        # to alias (tokens is [B, bucket + k]), so donating it just warns.
        return jax.jit(decode, donate_argnums=(1,))

    def __call__(
        self,
        input_ids,
        generation_config: Optional[GenerationConfig] = None,
        rng=None,
        attention_mask=None,
        **kwargs,
    ):
        """`attention_mask` ([B, prompt_len] 1/0) enables ragged batch prompts via
        the HF LEFT-padding convention: pads go at the START of each row. Rotary/
        learned positions come from the mask's cumsum (first real token = position
        0) and the pad slots stay masked for the whole decode via the cache's
        persistent pad mask."""
        config = generation_config or GenerationConfig(**kwargs)
        if rng is None:
            rng = _default_rng()
        # Host copy first (free for numpy/list inputs, one explicit drain for
        # device inputs): the host tail concatenates against it, so the prompt
        # matrix is never drained a second time after decode.
        ids_host = np.asarray(input_ids, np.int32)
        input_ids = jnp.asarray(ids_host)
        b, prompt_len = input_ids.shape
        max_new = min(config.max_new_tokens, self.max_length - prompt_len)
        if max_new <= 0:
            raise ValueError(
                f"Prompt length {prompt_len} leaves no room in the {self.max_length}-token cache"
            )
        if attention_mask is not None:
            # Validate on the HOST copy: an implicit bool() on a device value
            # would trip jax.transfer_guard("disallow") when a TraceGuard is
            # armed around this call (np.asarray is an explicit, sanctioned
            # step-boundary read).
            am_host = np.asarray(attention_mask)
            if am_host.ndim != 2 or am_host.shape != input_ids.shape:
                raise ValueError(
                    f"attention_mask must be [batch, prompt_len] matching input_ids "
                    f"{input_ids.shape}, got {am_host.shape}"
                )
            # LEFT padding only (prefill samples from the LAST slot's logits and
            # decode continues at each row's real length): a right-padded batch
            # would silently continue from a pad token's logits.
            if not (am_host[:, -1] == 1).all():
                raise ValueError(
                    "attention_mask looks right-padded (a row's last slot is 0); "
                    "Generator uses the HF LEFT-padding convention — put pads at "
                    "the START of each row"
                )
            am = jnp.asarray(am_host.astype(np.int32))
            # Host-side (numpy) position prep + ONE explicit push each: eager
            # jnp ops here would implicitly transfer their Python constants on
            # every call (and trip an armed TraceGuard).
            positions = _operand(np.clip(np.cumsum(am_host, axis=-1) - 1, 0, None), np.int32)
            # Per-row LOGICAL position base for decode: row with r real tokens
            # continues at position r (physical cache slots stay uniform).
            next_positions = _operand(am_host.sum(-1), np.int32)
            prefill_args = (input_ids, positions, am)
        else:
            positions = _operand(
                np.broadcast_to(np.arange(prompt_len)[None, :], (b, prompt_len)), np.int32
            )
            next_positions = _operand(np.full((b,), prompt_len), np.int32)
            prefill_args = (input_ids, positions)
        presence = None
        if config.repetition_penalty != 1.0:
            # Seed the seen-token set from the REAL prompt tokens (pad slots of a
            # left-padded batch must not mark token id 0 as seen).
            real = (
                am.astype(bool)
                if attention_mask is not None
                else jnp.ones((b, prompt_len), bool)
            )
            presence = (
                jnp.zeros((b, self.base_config.vocab_size), bool)
                .at[jnp.arange(b)[:, None], input_ids]
                .max(real)
            )
        params = self.params if "params" in self.params else {"params": self.params}
        logits, cache = self._prefill(params, *prefill_args)
        if config.draft_tokens:
            # Speculative loop operands: the history buffer (physical order —
            # prompt buffer incl. any left pads, then generated tokens) and its
            # base width. Fixed [B, max_length + k] shape, so varying prompt
            # lengths reuse the one compiled loop per bucket, like the cache.
            hist = np.zeros((b, self.max_length + config.draft_tokens), np.int32)
            hist[:, :prompt_len] = ids_host
            generated, _cache = self._decode_fn(_bucket_for(max_new), config)(
                params,
                cache,
                logits,
                next_positions,
                _operand(max_new, np.int32),
                jnp.asarray(hist),
                _operand(prompt_len, np.int32),
            )
        else:
            generated, _cache = self._decode_fn(_bucket_for(max_new), config)(
                params,
                cache,
                logits,
                next_positions,
                _operand(max_new, np.int32),
                _operand(config.temperature, np.float32),
                _operand(config.repetition_penalty, np.float32),
                rng,
                presence,
            )
        # Host tail entirely in numpy: even a static eager slice on a device
        # array dispatches dynamic_slice with implicitly-pushed start indices,
        # which an armed transfer guard rejects. One explicit drain (the host
        # read _trim_at_eos needs anyway; jax.device_get — np.asarray of a
        # device value is an implicit read on a TPU), trim, one explicit push back.
        gen_host = jax.device_get(generated)[:, :max_new]
        gen_host = _trim_at_eos(gen_host, config.eos_token_id, max_new)
        return jnp.asarray(np.concatenate([ids_host, gen_host], axis=1))


class Seq2SeqGenerator:
    """Compiled encode + fused decode loop for encoder-decoder Model bundles (T5):
    the encoder runs ONCE per prompt, then the same on-device `lax.while_loop`
    decode as `Generator`, with the encoder output riding along as a loop operand.

    The decoder module must expose `encode(input_ids, attention_mask)` and
    `decode(decoder_input_ids, encoder_hidden, positions, enc_mask)` methods plus a
    `decode_cache_length` config field (models/t5.py is the in-tree shape)."""

    def __init__(self, model, max_new_tokens: int = 32, decoder_start_token_id: int = 0):
        module = getattr(model, "module", None)
        if module is None or not hasattr(module, "encode"):
            raise ValueError("Seq2SeqGenerator needs a Model bundle with an encoder-decoder flax module")
        self.base_config = module.config
        self.params = model.params if "params" in model.params else {"params": model.params}
        self.max_new_tokens = max_new_tokens
        self.start_id = decoder_start_token_id
        decode_cfg = dataclasses.replace(module.config, decode_cache_length=max_new_tokens + 1)
        self.module = type(module)(decode_cfg, use_cache=True)
        mod = self.module
        resolve = _params_resolver(model)

        def encode(params, input_ids, attention_mask):
            return mod.apply(resolve(params), input_ids, attention_mask, method="encode")

        def prime(params, encoder_hidden, enc_mask, start_tokens):
            # Write the start token at decoder position 0 and return its logits.
            logits, mutated = mod.apply(
                resolve(params),
                start_tokens[:, None],
                encoder_hidden,
                jnp.zeros((1,), jnp.int32),
                enc_mask,
                mutable=["cache"],
                method="decode",
            )
            return logits[:, -1, :], mutated["cache"]

        def step(params, cache, token, position, encoder_hidden, enc_mask):
            logits, mutated = mod.apply(
                {**resolve(params), "cache": cache},
                token[:, None],
                encoder_hidden,
                position[:1],  # decoder positions are shared across the batch
                enc_mask,
                mutable=["cache"],
                method="decode",
            )
            return logits[:, -1, :], mutated["cache"]

        self._encode = jax.jit(encode)
        self._prime = jax.jit(prime)
        self._step_inner = step  # traced inside the fused decode loop
        self._decode_cache = {}

    _decode_fn = Generator._decode_fn  # same bucketed fused-loop builder

    def __call__(self, input_ids, generation_config: Optional[GenerationConfig] = None, rng=None, **kwargs):
        attention_mask = kwargs.pop("attention_mask", None)  # before GenerationConfig(**kwargs)
        explicit_request = generation_config is not None or "max_new_tokens" in kwargs
        config = generation_config or GenerationConfig(**kwargs)
        if config.draft_tokens:
            raise ValueError(
                "speculative decoding (draft_tokens > 0) is causal-LM only; the "
                "encoder-decoder decode path has no verify-block seam"
            )
        if rng is None:
            rng = _default_rng()
        input_ids = jnp.asarray(input_ids, jnp.int32)
        b = input_ids.shape[0]
        enc_mask = (
            jnp.asarray(np.asarray(attention_mask, bool))[:, None, None, :]
            if attention_mask is not None
            else _operand(np.ones((b, 1, 1, input_ids.shape[1])), bool)
        )
        max_new = config.max_new_tokens
        if not explicit_request:
            # Bare call: the dataclass default (32) is not a user request — fill
            # whatever budget this generator was built with.
            max_new = min(max_new, self.max_new_tokens)
        elif max_new > self.max_new_tokens:
            raise ValueError(
                f"Requested {max_new} new tokens but this generator's decoder "
                f"cache was sized for {self.max_new_tokens}; rebuild with a larger max_new_tokens"
            )
        am = jnp.asarray(attention_mask, jnp.int32) if attention_mask is not None else None
        encoder_hidden = self._encode(self.params, input_ids, am)
        start = _operand(np.full((b,), self.start_id), np.int32)
        first_logits, cache = self._prime(self.params, encoder_hidden, enc_mask, start)
        presence = None
        if config.repetition_penalty != 1.0:
            # Encoder-decoder penalty covers the DECODER context (HF semantics):
            # seed with the start token only.
            presence = (
                jnp.zeros((b, self.base_config.vocab_size), bool)
                .at[jnp.arange(b), start]
                .set(True)
            )
        generated, _cache = self._decode_fn(_bucket_for(max_new), config)(
            self.params,
            cache,
            first_logits,
            _operand(1, np.int32),  # the start token occupies cache position 0
            _operand(max_new, np.int32),
            _operand(config.temperature, np.float32),
            _operand(config.repetition_penalty, np.float32),
            rng,
            presence,
            encoder_hidden,
            enc_mask,
        )
        # numpy host tail (see Generator.__call__): drain once, trim, push back.
        gen_host = jax.device_get(generated)[:, :max_new]
        gen_host = _trim_at_eos(gen_host, config.eos_token_id, max_new)
        return jnp.asarray(gen_host)  # decoder tokens only (HF seq2seq generate shape)


# Warm-executable cache for the module-level generate() convenience: keyed on the
# MODEL'S identity (weakly — a dead model must not pin its Generator, and a reused
# id() must not serve another model's programs) plus any Generator kwargs.
# max_new_tokens is NOT part of the key: the Generator's cache capacity comes from
# max_length/max_position_embeddings and the fused loop buckets per call, so one
# cached Generator serves every budget. A hit also requires `model.params` to be
# the SAME object the Generator holds — `model.params = new_params` (the
# train-then-sample pattern) must rebuild, never decode with stale weights.
# Without the cache every convenience call paid a fresh prefill+decode compile
# (~seconds) for byte-identical programs.
_GENERATOR_CACHE: dict = {}
_GENERATOR_CACHE_MAX = 8
# generate() was stateless (and so trivially thread-safe) before the cache; the
# lock covers only dict bookkeeping — Generator construction/compilation runs
# outside it (two racing misses both build; last insert wins).
_GENERATOR_CACHE_LOCK = threading.Lock()


def _evict_dead_generator_entries(dead_ref):
    """weakref finalizer: a collected model must not pin its Generator (params
    device buffers + compiled executables) until an id()-colliding lookup or LRU
    overflow happens to evict it."""
    with _GENERATOR_CACHE_LOCK:
        for key in [k for k, (r, _) in _GENERATOR_CACHE.items() if r is dead_ref]:
            _GENERATOR_CACHE.pop(key, None)


def _cached_generator(model, max_new_tokens: int, **kwargs) -> Generator:
    import weakref

    key = (id(model), tuple(sorted(kwargs.items())))
    with _GENERATOR_CACHE_LOCK:
        hit = _GENERATOR_CACHE.get(key)
        if hit is not None:
            ref, generator = hit
            if ref() is model and generator.params is model.params:
                _GENERATOR_CACHE[key] = _GENERATOR_CACHE.pop(key)  # LRU bump
                return generator
            _GENERATOR_CACHE.pop(key, None)  # dead/reused id() or rebound params
    generator = Generator(model, max_new_tokens=max_new_tokens, **kwargs)
    try:
        ref = weakref.ref(model, _evict_dead_generator_entries)
    except TypeError:  # non-weakref-able bundle: don't cache rather than leak
        return generator
    with _GENERATOR_CACHE_LOCK:
        _GENERATOR_CACHE[key] = (ref, generator)
        while len(_GENERATOR_CACHE) > _GENERATOR_CACHE_MAX:
            del _GENERATOR_CACHE[next(iter(_GENERATOR_CACHE))]
    return generator


def generate(model, input_ids, max_new_tokens: int = 32, **kwargs):
    """One-shot convenience: build (or reuse — see `_cached_generator`) a
    Generator and run it (HF `model.generate` shape)."""
    gen_kwargs = {
        k: kwargs.pop(k)
        for k in ("do_sample", "temperature", "top_k", "top_p", "repetition_penalty",
                  "eos_token_id", "pad_token_id", "draft_tokens", "draft_ngram")
        if k in kwargs
    }
    attention_mask = kwargs.pop("attention_mask", None)
    generator = _cached_generator(model, max_new_tokens, **kwargs)
    return generator(
        input_ids,
        GenerationConfig(max_new_tokens=max_new_tokens, **gen_kwargs),
        attention_mask=attention_mask,
    )

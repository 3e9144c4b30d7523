"""One layer's cached-prefill attention by DEVICE time: `ops.frontier_attention`
(the kernel that stops at the causal frontier) against the masked XLA product
over the whole window, at a latent (MLA) serving cell's shapes.

Run it on the chip, from the root of a checkout:

    chiprun -- python benchmarks/frontier_attention_timer.py            # the table
    chiprun -- python benchmarks/frontier_attention_timer.py --sweep    # block sizes

Defaults are `xing4-29b-a4b.prompt-heavy-saturated`'s: 32 heads, a window of
2,176 positions, keys of 128 + 64 shared rotary, values of 128, bfloat16;
`--heads 16 --window 2048` is `kimi-vl-a3b.decode-heavy-saturated`'s. Every
row of the table is one (rows, cur): the cache's index 0 (no matched prefix)
and one that is no multiple of a block. Times are sums of the device's own
`XLA Ops` events over a capture of `--calls` calls, never the host's clock;
`tflops` is the products the mask KEEPS (`rows * (cur + (rows + 1) / 2)` keys
a head) over that time. Prints one JSON line a row.

Off a TPU nothing is timed: the kernel (interpreted) is held to the XLA
product at a small shape and the script says so.
"""

import argparse
import glob
import json
import os
import shutil
import sys
import tempfile

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from accelerate_tpu.ops import frontier_attention as frontier  # noqa: E402
from accelerate_tpu.ops.attention import dot_product_attention  # noqa: E402


def operands(rows, window, heads, key_dim, shared_dim, value_dim, dtype, seed=0):
    """q [1, rows, H, Dk + Ds], k_nope [1, L, H, Dk], k_pe [1, L, Ds], v [1, L, H, Dv] as a model holds them."""
    keys = jax.random.split(jax.random.key(seed), 4)
    normal = lambda key, shape: jax.random.normal(key, shape, jnp.float32).astype(dtype)  # noqa: E731
    return (normal(keys[0], (1, rows, heads, key_dim + shared_dim)), normal(keys[1], (1, window, heads, key_dim)),
            normal(keys[2], (1, window, shared_dim)), normal(keys[3], (1, window, heads, value_dim)))


def masked_xla(q, k_nope, k_pe, v, cur, scale):
    """What `models/latent_moe.py` runs off a TPU: the shared part broadcast to
    the heads, every position of the window scored, `update_decode_cache`'s mask."""
    rows, window, heads = q.shape[1], v.shape[1], v.shape[2]
    k = jnp.concatenate([k_nope, jnp.broadcast_to(k_pe[:, :, None, :], (1, window, heads, k_pe.shape[-1]))], axis=-1)
    at = cur + jnp.arange(rows)[:, None]
    cols = jnp.arange(window)[None, :]
    mask = ((cols <= at) & (cols < cur + rows))[None, None]
    out = dot_product_attention(q, k, v, mask=mask, scale=scale, causal=False, implementation="xla")
    return out.reshape(1, rows, -1)


def head_major(q, k_nope, k_pe, v):
    """The kernel's operands: queries `[1, H, Dk + Ds, rows]` and values `[1, H, Dv, L]` transposed, keys
    `[1, H, L, Dk]` — layouts the model's own products write."""
    return q.transpose(0, 2, 3, 1), k_nope.transpose(0, 2, 1, 3), k_pe, v.transpose(0, 2, 3, 1)


def walked(q_t, k_nope, k_pe, v_t, cur, scale):
    return frontier.frontier_attention(q_t, k_nope, v_t, cur, scale=scale, shared_k=k_pe)


def device_ms(fn, args, calls):
    """Device milliseconds a call: the `XLA Ops` line of the first TPU plane, summed over `calls` calls."""
    jax.block_until_ready(fn(*args))
    directory = tempfile.mkdtemp(prefix="frontier_trace_")
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 0
    jax.profiler.start_trace(directory, profiler_options=options)
    try:
        for _ in range(calls):
            out = fn(*args)
        jax.block_until_ready(out)
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(os.path.join(directory, "plugins", "profile", "*", "*.xplane.pb"))[0]
    profile = jax.profiler.ProfileData.from_file(path)
    shutil.rmtree(directory, ignore_errors=True)
    plane = sorted((p for p in profile.planes if p.name.startswith("/device:TPU:")), key=lambda p: p.name)[0]
    by_name = {}
    for line in plane.lines:
        if line.name == "XLA Ops":
            for event in line.events:
                by_name[event.name] = by_name.get(event.name, 0.0) + event.duration_ns
    return sum(by_name.values()) / calls / 1e6, {
        name.split(" = ")[0][:40]: round(ns / calls / 1e6, 4) for name, ns in sorted(by_name.items(), key=lambda kv: -kv[1])[:4]}


def main():
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--heads", type=int, default=32)
    parser.add_argument("--window", type=int, default=2176)
    parser.add_argument("--rows", type=int, nargs="+", default=[256, 512, 1024, 2048])
    parser.add_argument("--cur", type=int, nargs="+", default=[0, 100])
    parser.add_argument("--calls", type=int, default=10)
    parser.add_argument("--sweep", action="store_true", help="the kernel alone at several (BLOCK_Q, BLOCK_K)")
    args = parser.parse_args()
    key_dim, shared_dim, value_dim = 128, 64, 128
    scale = (key_dim + shared_dim) ** -0.5
    device = jax.devices()[0]
    if device.platform != "tpu":
        ops = operands(64, 256, 2, key_dim, shared_dim, value_dim, jnp.float32)
        gap = float(jnp.max(jnp.abs(
            walked(*head_major(*ops), jnp.int32(37), scale) - masked_xla(*ops, jnp.int32(37), scale))))
        print(json.dumps({"device": device.platform, "timed": False, "interpreted_kernel_against_xla_max_abs": gap}))
        return
    xla = jax.jit(masked_xla, static_argnums=5)
    blocks = ([(256, 256), (256, 512), (512, 512), (512, 1024), (1024, 512)]
              if args.sweep else [(frontier.BLOCK_Q, frontier.BLOCK_K)])
    for rows in args.rows:
        for cur in args.cur:
            if cur + rows > args.window:
                continue
            ops = operands(rows, args.window, args.heads, key_dim, shared_dim, value_dim, jnp.bfloat16)
            at, by_head = jnp.int32(cur), jax.block_until_ready(head_major(*ops))
            kept = args.heads * rows * (cur + (rows + 1) / 2) * (key_dim + shared_dim + value_dim) * 2
            line = {"device": device.device_kind, "heads": args.heads, "window": args.window, "rows": rows, "cur": cur,
                    "kept_gflop": round(kept / 1e9, 2)}
            if not args.sweep:
                ms, by = device_ms(xla, (*ops, at, scale), args.calls)
                line.update(xla_ms=round(ms, 4), xla_tflops=round(kept / ms / 1e9, 1), xla_ops=by)
                want = np.asarray(xla(*ops, at, scale), np.float32)
            for block_q, block_k in blocks:
                # No outer jit: the call's own inner one takes the block sizes as statics.
                frontier.BLOCK_Q, frontier.BLOCK_K = block_q, block_k
                ms, by = device_ms(walked, (*by_head, at, scale), args.calls)
                tag = f"kernel_{block_q}x{block_k}" if args.sweep else "kernel"
                line.update({f"{tag}_ms": round(ms, 4), f"{tag}_tflops": round(kept / ms / 1e9, 1)})
                if not args.sweep:
                    line.update(kernel_ops=by, xla_over_kernel=round(line["xla_ms"] / ms, 2),
                                max_abs_gap=float(np.max(np.abs(np.asarray(walked(*by_head, at, scale), np.float32) - want))),
                                key_blocks=frontier.frontier_key_blocks(cur, rows, args.window))
            print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()

"""Bytes the active slots hold — the by-slot state of the slots a chunk updates
(`state_slots` x `state_bytes_per_slot`) plus their live pages (`live_pages` x
`kv_page_bytes`), all read off the live cache's shapes by the engine — over
what full attention in every layer would hold for the same tokens: the client
loop's own count of the tokens in flight, times
`shapes_hybrid.full_attention_kv_bytes_per_token`. Both are means over the
measured window. ~57 at a live context of ~345 tokens a slot; it falls as
contexts grow, towards the 25 of the pages alone. The guard against a padded
or widened state, and the number that says what the hybrid saves at this
length."""

from chipbench import chunk_counters, shapes_hybrid

NEEDS = ("state_slots", "state_bytes_per_slot", "live_pages", "kv_page_bytes")


def read(context):
    counted = chunk_counters.chunks(context, NEEDS)
    window = context["window"]
    tokens = [s[4] for s in window["steps"] if window["t0"] <= s[0] < window["t1"]]
    if not counted or not tokens or not sum(tokens):
        return None
    held = sum(a["state_slots"] * a["state_bytes_per_slot"] + a["live_pages"] * a["kv_page_bytes"]
               for a in counted) / len(counted)
    cell = context["cell"]
    full = sum(tokens) / len(tokens) * shapes_hybrid.full_attention_kv_bytes_per_token(cell.config, cell.spec["dtype"])
    return held / full * 100.0

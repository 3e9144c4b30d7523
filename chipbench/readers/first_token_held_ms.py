"""How long the engine held a first token it already had on the host before
the `step()` that carries it returned: median `held_s` of the `handed_back`
event of the `serve.request` spans submitted in the window, after the capture.
Below the knee it is the decode chunk that the insert's step still runs."""

from chipbench import harness, program_spans


def read(context):
    held = program_spans.request_events(context, "handed_back", "held_s")
    return harness.median(held) * 1e3 if held else None

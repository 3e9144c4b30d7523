"""Share of the engine's slots in use, time-averaged over the window: the
engine's `slots_in_use` as the client loop sampled it after every `step()`."""

from chipbench import harness


def read(context):
    window = context["window"]
    samples = [(b - a, slots / context["num_slots"] * 100.0)
               for a, b, slots, *_ in window["steps"] if a >= window["t0"]]
    return harness.time_weighted_mean(samples) if samples else None

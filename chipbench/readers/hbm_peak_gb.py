"""Peak device memory of the fullest chip, as the device's `memory_stats()`
report it when the window closes (before the reference runs)."""


def read(context):
    return context["peak_bytes"] / 1e9

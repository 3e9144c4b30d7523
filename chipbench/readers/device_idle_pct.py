"""Share of the traced window in which no operation ran on the device: 1 minus
the union of the device's operation intervals over the window, chips averaged."""


def read(context):
    reduced = context["trace"]
    return (1.0 - reduced["busy_s"] / reduced["window_s"]) * 100.0

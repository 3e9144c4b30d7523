"""Backend compilations (fetches from the persistent cache included) that JAX
reported between the window's first and last instant. Expected: 0."""


def read(context):
    return float(context["compiles_in_window"])

"""Peak share of the page pool referenced by in-flight requests in the window
(the engine's `pool.pages_in_use` over `pool.pages_total`)."""


def read(context):
    window = context["window"]
    pages = [p for a, _b, _slots, p, *_ in window["steps"] if a >= window["t0"]]
    if not pages or not context["pages_total"]:
        return None
    return max(pages) / context["pages_total"] * 100.0

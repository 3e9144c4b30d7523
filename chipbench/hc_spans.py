"""What the three `hc` / `latent_hc` readers share: the captured stretch's
`serve.insert` and `serve.decode_chunk` spans of a program that serves a
residual path of several streams (`hc_rows` on both), or None of a program
that has no such counter (a parent commit) or a capture that holds none."""

from chipbench import captured_spans


def spans(context: dict, name: str) -> list | None:
    placed = captured_spans.place(captured_spans.captured(context))
    if placed is None:
        return None
    attrs = [r["attrs"] for r in captured_spans.spans(name, placed)]
    return attrs if attrs and all("hc_rows" in a for a in attrs) else None


def mix_seconds(reduced: dict) -> float:
    """Device time of the operations named `hc_pre*` / `hc_post*` (the program's two Pallas kernels)."""
    return sum(seconds for name, seconds in reduced["device_ops"] if name.startswith(("hc_pre", "hc_post")))

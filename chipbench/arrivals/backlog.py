"""`{"kind": "backlog"}`: every request is due at once; the client loop keeps
the engine's queue full for the whole window."""


def gaps(arrivals: dict, n: int):
    return None

"""Requests per launched batch, over the batches of the window."""


def read(run):
    b = [r["bucket"] for r in run.records]
    return sum(b) / len(b) if b else None

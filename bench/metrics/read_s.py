"""Seconds per scan in the program's `stage.read` span: the projections
read from the store and put on the device (io/streams.py
ProjectionSource.load)."""


def read(run):
    spans = run.spans.get("stage.read")
    return sum(spans) / len(spans) if spans else None

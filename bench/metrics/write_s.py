"""Seconds per scan in the program's `stage.write` span: the volume taken
off the device and stored (io/streams.py VolumeSink.write)."""


def read(run):
    spans = run.spans.get("stage.write")
    return sum(spans) / len(spans) if spans else None

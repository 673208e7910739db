"""From a `jax.profiler` trace of the measured window to per-layer numbers.

Two steps, kept apart so that the second can be checked on a recorded trace:

1. `load()` reads the profiler's `.xplane.pb` into a plain dict: for each
   device, its XLA operations as [name, opcode, scope, start_ns,
   duration_ns]; and the host's `bench.scan` annotations, one per scan of
   the window, as [start_ns, end_ns]. On a TPU an operation's event is
   named by its HLO text, `%backproject_dual.4 = f32[...] custom-call(...)`,
   from which `name` and `opcode` are parsed. The trace carries no
   metadata: `scope`, the name of the JAX operation that made the XLA
   operation, comes from the compiled module's text (`scopes_from_hlo`).
2. The functions below reduce that dict. An operation is matched by its
   opcode, or by the name of the kernel or of the JAX operation it came
   from, never by its position.

Times are those of the trace's own clock, in nanoseconds.
"""
from __future__ import annotations

import glob
import gzip
import json
import os
import re

# The annotation the harness wraps around each scan of the window.
SCAN = "bench.scan"

# The Pallas back-projection kernel: `pallas_call(name="backproject_dual")`,
# whose name XLA gives its custom call.
BP_KERNEL = "backproject_dual"

# The FFT: XLA:CPU keeps the `fft` opcode; XLA:TPU lowers it to DFT
# products, whose metadata keep the JAX operation's name, `jit(fft)`. Only
# the ramp filter takes FFTs.
FFT_OPCODES = frozenset({"fft"})
FFT_SCOPE = "jit(fft)"

_SUFFIX = re.compile(r"(\.\d+)+$")
_HLO_LINE = re.compile(
    r'^\s*(?:ROOT )?%([\w.\-]+) = .*?metadata=\{[^}]*?op_name="([^"]*)"')


def parse_op(text: str) -> tuple:
    """(name, opcode) of an operation's event: the HLO text of a TPU
    event, `%fusion.12 = (f32[8], s32[]) fusion(...), ...`, or a bare
    name, `fft.0`, whose opcode is the name without its numeric suffix."""
    if " = " not in text:
        name = text.lstrip("%")
        return name, _SUFFIX.sub("", name)
    lhs, rest = text.split(" = ", 1)
    if rest.startswith("("):              # a tuple shape: skip it whole
        depth = 0
        for end, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                break
        rest = rest[end + 1:]
    else:
        rest = rest.split(" ", 1)[1] if " " in rest else ""
    return lhs.strip().lstrip("%"), rest.strip().split("(", 1)[0]


def scopes_from_hlo(text: str) -> dict:
    """{instruction name: metadata op_name} of a compiled module's text."""
    out = {}
    for line in text.splitlines():
        m = _HLO_LINE.match(line)
        if m:
            out[m.group(1)] = m.group(2)
    return out


def is_bp_kernel(name: str, opcode: str, scope: str) -> bool:
    return _SUFFIX.sub("", name) == BP_KERNEL


def is_fft(name: str, opcode: str, scope: str) -> bool:
    return opcode in FFT_OPCODES or FFT_SCOPE in scope


# -- step 1: the profiler's file -> a plain dict ------------------------------

def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir!r}")
    return found[-1]


def _stats(event) -> dict:
    try:
        return dict(event.stats)
    except (TypeError, ValueError):
        return {}


def load(trace_dir: str, scopes: dict | None = None) -> dict:
    """{"ops": {device: [[name, opcode, scope, start_ns, dur_ns], ...]},
        "scans": [[start_ns, end_ns], ...]} from the trace in `trace_dir`;
    `scopes` maps instruction names to the JAX operations that made them."""
    from jax.profiler import ProfileData

    scopes = scopes or {}
    data = ProfileData.from_file(find_xplane(trace_dir))
    ops: dict = {}
    scans = []

    def row(ev):
        name, opcode = parse_op(ev.name)
        return [name, opcode, scopes.get(name, ""), int(ev.start_ns),
                int(ev.duration_ns)]

    for plane in data.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops.setdefault(plane.name, []).extend(
                        row(ev) for ev in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == SCAN:
                        scans.append([int(ev.start_ns),
                                      int(ev.start_ns + ev.duration_ns)])
                    elif (plane.name == "/host:CPU"
                          and "hlo_op" in _stats(ev)):
                        # XLA:CPU runs its operations on host threads.
                        ops.setdefault("/host:CPU", []).append(row(ev))
    if any(dev.startswith("/device:") for dev in ops):
        ops.pop("/host:CPU", None)
    scans.sort()
    return {"ops": ops, "scans": scans}


def save(trace: dict, path: str) -> None:
    with gzip.open(path, "wt") as f:
        json.dump(trace, f)


def read(path: str) -> dict:
    with gzip.open(path, "rt") as f:
        return json.load(f)


# -- step 2: reductions -------------------------------------------------------

def window(trace: dict) -> tuple:
    """(start_ns, end_ns) from the first scan's start to the last's end."""
    scans = trace["scans"]
    if not scans:
        raise ValueError("the trace holds no bench.scan annotation")
    return scans[0][0], scans[-1][1]


def _clipped(rows, lo: int, hi: int):
    for name, opcode, scope, start, dur in rows:
        a, b = max(start, lo), min(start + dur, hi)
        if b > a:
            yield name, opcode, scope, a, b


def device_seconds(trace: dict, match) -> dict:
    """{device: seconds} of the operations `match(name, opcode, scope)`
    accepts, inside the window; devices where none matched are left out."""
    lo, hi = window(trace)
    out = {}
    for dev, rows in trace["ops"].items():
        total = sum(b - a for name, opcode, scope, a, b
                    in _clipped(rows, lo, hi) if match(name, opcode, scope))
        if total:
            out[dev] = total / 1e9
    return out


def busy_intervals(rows, lo: int, hi: int) -> list:
    """The union of the operations' intervals inside [lo, hi), merged."""
    spans = sorted((a, b) for *_, a, b in _clipped(rows, lo, hi))
    merged: list = []
    for a, b in spans:
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return merged


def busy_seconds(trace: dict) -> dict:
    """{device: seconds in which some operation ran}, inside the window."""
    lo, hi = window(trace)
    return {dev: sum(b - a for a, b in busy_intervals(rows, lo, hi)) / 1e9
            for dev, rows in trace["ops"].items()}


def idle_gaps(trace: dict, host_spans: dict) -> list:
    """The idle stretches of every device inside the window, cut where the
    host's activity changes, longest first, as [label, seconds]. The label
    is the host span that covers the piece (`host_spans`: {label:
    [[start_ns, end_ns], ...]} on the trace's clock), "between scans"
    outside every scan, or "in scan"."""
    lo, hi = window(trace)
    scans = trace["scans"]
    edges = sorted({t for spans in list(host_spans.values()) + [scans]
                    for span in spans for t in span})

    def label(t):
        for name, spans in host_spans.items():
            if any(s <= t < e for s, e in spans):
                return name
        if any(s <= t < e for s, e in scans):
            return "in scan"
        return "between scans"

    pieces = []
    for rows in trace["ops"].values():
        edge = lo
        for a, b in busy_intervals(rows, lo, hi) + [[hi, hi]]:
            if a > edge:
                cuts = [edge] + [t for t in edges if edge < t < a] + [a]
                pieces += [[label((x + y) / 2), (y - x) / 1e9]
                           for x, y in zip(cuts, cuts[1:])]
            edge = max(edge, b)
    pieces.sort(key=lambda row: -row[1])
    return pieces


def top_ops(trace: dict, n: int = 10) -> list:
    """The `n` operations that took most device time in the window, as
    [name, seconds per device], summed over their runs. A `while` counts
    the operations of its body too."""
    lo, hi = window(trace)
    totals: dict = {}
    for rows in trace["ops"].values():
        for name, *_, a, b in _clipped(rows, lo, hi):
            totals[name] = totals.get(name, 0) + (b - a)
    n_dev = max(len(trace["ops"]), 1)
    ranked = sorted(totals.items(), key=lambda kv: -kv[1])[:n]
    return [[name, t / 1e9 / n_dev] for name, t in ranked]


def per_scan(trace: dict, match) -> float | None:
    """Seconds per scan of the operations `match` accepts, the mean over
    the devices of the trace; None where no operation matched."""
    seconds = device_seconds(trace, match)
    if not seconds:
        return None
    return sum(seconds.values()) / len(trace["ops"]) / len(trace["scans"])

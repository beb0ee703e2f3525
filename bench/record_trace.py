"""Record a small profiler trace of a cell's sweep on the chip, for the
trace-reduction tests.

    python3 bench/record_trace.py bench/testdata/NAME.xplane.pb.gz \
        [--workload noc6x6.fig9] [--epochs 1] [--cycles 8] [--seed 0]

The cell's configuration is cut to `--epochs` x `--cycles` so the trace
stays small; one warm-up step compiles, then one step is traced inside the
host span `bench.window` with the profiler options of `bench/run.py
--trace 1`, and the `.xplane.pb`, cut to what the trace reduction reads
(`prune`), is written gzipped.  Needs a TPU: it exits 3 without one.
"""
from __future__ import annotations

import argparse
import copy
import glob
import gzip
import os
import shutil
import sys
import tempfile
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def _varint(buf: bytes, i: int) -> tuple[int, int]:
    value = shift = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        shift += 7
        if b < 0x80:
            return value, i


def _fields(buf: bytes):
    """(field number, the field's bytes, its payload if length-delimited,
    its value if a varint) for each field of a protobuf message."""
    i = 0
    while i < len(buf):
        start = i
        key, i = _varint(buf, i)
        num, wire = key >> 3, key & 7
        payload = value = None
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 1:
            i += 8
        elif wire == 2:
            n, i = _varint(buf, i)
            payload = buf[i:i + n]
            i += n
        elif wire == 5:
            i += 4
        else:
            raise ValueError(f"protobuf wire type {wire} in an XSpace")
        yield num, buf[start:i], payload, value


def _message(num: int, payload: bytes) -> bytes:
    out, n = bytearray(), len(payload)
    for v in ((num << 3) | 2, n):
        while v >= 0x80:
            out.append(v & 0x7F | 0x80)
            v >>= 7
        out.append(v)
    return bytes(out) + payload


def _name(msg: bytes, num: int = 2) -> str:
    for n, _, payload, _ in _fields(msg):
        if n == num:
            return payload.decode()
    return ""


def _only(msg: bytes, nums) -> bytes:
    """The message with only its fields numbered in `nums`."""
    return b"".join(f for n, f, _, _ in _fields(msg) if n in nums)


# What the trace reduction reads of an XSpace, by field number
# (xplane.proto).  XSpace: 1 planes.  XPlane: 1 id, 2 name, 3 lines, 4
# event metadata (a map entry: 1 id, 2 XEventMetadata: 1 id, 2 name).
# XLine: 1 id, 2 name, 3 start, 4 events, 9 duration, 11 display name.
# XEvent: 1 metadata id, 2 offset, 3 duration, 5 occurrences; 4, its
# statistics, goes.
LINE_FIELDS = {1, 2, 3, 9, 11}
EVENT_FIELDS = {1, 2, 3, 5}


def prune(xspace: bytes, window: str = "bench.window") -> bytes:
    """The serialized XSpace cut to what `bench/trace_reduce.py` reads: each
    TPU plane's `XLA Ops` line and the host line that holds the span
    `window`, their events' times and names, nothing else.  The profiler's
    other planes, threads and statistics are most of a capture's size."""
    import jax

    from bench import trace_reduce

    planes = jax.profiler.ProfileData.from_serialized_xspace(xspace).planes
    _, host_line = trace_reduce.find_window(list(planes), window)
    out = bytearray()
    for num, _, plane, _ in _fields(xspace):
        if num != 1:
            continue
        name = _name(plane)
        if trace_reduce.DEVICE_PLANE.match(name):
            keep = trace_reduce.OPS_LINE
        elif name == "/host:CPU":
            keep = host_line.name
        else:
            continue
        lines, used = bytearray(), set()
        for n, _, line, _ in _fields(plane):
            if n != 3 or _name(line) != keep:
                continue
            body = bytearray(_only(line, LINE_FIELDS))
            for m, _, event, _ in _fields(line):
                if m == 4:
                    used |= {v for k, _, _, v in _fields(event) if k == 1}
                    body += _message(4, _only(event, EVENT_FIELDS))
            lines += _message(3, bytes(body))
        kept = bytearray(_only(plane, {1, 2}))
        for n, _, entry, _ in _fields(plane):
            if n == 4 and _only_value(entry, 1) in used:
                meta = _only(_only_payload(entry, 2), {1, 2})
                kept += _message(4, _only(entry, {1}) + _message(2, meta))
        out += _message(1, bytes(kept + lines))
    return bytes(out)


def _only_value(msg: bytes, num: int):
    return next(v for n, _, _, v in _fields(msg) if n == num)


def _only_payload(msg: bytes, num: int) -> bytes:
    return next(p for n, _, p, _ in _fields(msg) if n == num)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("out")
    ap.add_argument("--workload", default="noc6x6.fig9")
    ap.add_argument("--epochs", type=int, default=1)
    ap.add_argument("--cycles", type=int, default=8)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    os.environ.setdefault("TPU_LOG_DIR", os.path.join(
        tempfile.gettempdir(), "tpu_logs"))
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax

    from bench import run

    if jax.devices()[0].platform != "tpu":
        run.log(f"[device] needs a TPU; JAX found {jax.devices()[0].platform}")
        return 3
    bench = run.Bench(ROOT)
    cell = bench.cell(args.workload)
    config = copy.deepcopy(bench.config(cell["config"]))
    config["noc"].update(n_epochs=args.epochs, epoch_len=args.cycles)
    traffic = bench.traffic(cell["traffic"])
    runner = bench.entry(traffic["entry"]).Runner(
        config, traffic, chips=cell["chips"], seed=args.seed)
    runner.step(0)

    trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    options.enable_hlo_proto = False
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    with jax.profiler.TraceAnnotation("bench.window"):
        runner.step(1)
    jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                        recursive=True)
    with open(path, "rb") as f:
        raw = f.read()
    shutil.rmtree(trace_dir, ignore_errors=True)
    with gzip.open(args.out, "wb") as out:
        out.write(prune(raw))
    run.log(f"[record] {args.out}: {os.path.getsize(args.out)} bytes")
    return 0


if __name__ == "__main__":
    sys.exit(main())

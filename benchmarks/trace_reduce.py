"""From a profiler trace (`.xplane.pb`) to the numbers the benchmark
reports: device busy and idle time, device time per compiled program, the
device operations that took most time, and the longest idle gaps by what
the host was doing.

Kept with the benchmark so every PR computes the same numbers the same
way. `selfcheck.py` checks it against the small recorded trace in
`traces/`. How a v5e trace is laid out (which planes are devices, what the
fused step and the gather are called) is written down in PERF.md section 3.

    python benchmarks/trace_reduce.py --inspect <file.xplane.pb>
    python benchmarks/trace_reduce.py --trim <in.xplane.pb> <out.xplane.pb> \
        --from-ms 100 --to-ms 160
"""
from __future__ import annotations

import glob
import os
import re
import sys

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
WINDOW = "bench.window"


def find_xplane(trace_dir: str) -> str:
    files = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(files, key=os.path.getmtime)


def load(path: str, device_lines=None) -> dict:
    """{plane name: {line name: [(event name, start_ns, duration_ns)]}};
    lines of one name in one plane (host threads) are kept apart by a
    `#n` suffix. `device_lines` keeps only those lines of device planes
    (a 20 s trace has a million events on lines the reduction never
    reads)."""
    from jax.profiler import ProfileData
    planes = {}
    for plane in ProfileData.from_file(path).planes:
        lines = planes.setdefault(plane.name, {})
        is_device = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if device_lines and is_device and line.name not in device_lines:
                continue
            name, n = line.name, 1
            while name in lines:
                n += 1
                name = f"{line.name}#{n}"
            lines[name] = [(e.name, float(e.start_ns), float(e.duration_ns))
                           for e in line.events]
    return planes


def program_name(event_name: str) -> str:
    """`jit_step(7253...)` -> `jit_step`."""
    return event_name.split("(", 1)[0]


_SHAPE = re.compile(r"[a-z]+\d*\[[\d,]*\]")
_OPCODE = re.compile(r"\)?\s([a-z][a-z\-]*)\(")


def short_op(text: str, limit: int = 120) -> str:
    """An HLO instruction as the trace names it, cut to what a reader
    needs: `%fusion.13 fusion f32[4687216,512] <- f32[4687216,512],
    s32[131072], f32[131072,512]` (name, opcode, result, operands)."""
    if " = " not in text:
        return text[:limit]
    name, rest = text.split(" = ", 1)
    m = _OPCODE.search(rest)
    if not m:
        return text[:limit]
    res = _SHAPE.findall(rest[:m.start() + 1])
    args = _SHAPE.findall(rest[m.end():])
    out = f"{name} {m.group(1)} " + (
        res[0] if len(res) == 1 else "(" + ", ".join(res[:4]) + ")")
    if args:
        out += " <- " + ", ".join(args[:4])
    return out[:limit]


def _union(intervals):
    """Merged, sorted [(start, end)] of possibly overlapping intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _clip(events, t0, t1):
    return [(n, max(s, t0), min(s + d, t1)) for n, s, d in events
            if s + d > t0 and s < t1]


def reduce(planes: dict, chips: int = None) -> dict:
    """The reduction. Times in seconds. The window is the harness's
    `bench.window` annotation where the host plane has it, else the span
    of the device events."""
    dev_names = sorted((p for p in planes if DEVICE_PLANE.match(p)),
                       key=lambda p: int(DEVICE_PLANE.match(p).group(1)))
    if chips:
        dev_names = dev_names[:chips]
    host = planes.get(HOST_PLANE, {})
    win = [(s, s + d) for ev in host.values() for n, s, d in ev
           if n == WINDOW]
    if win:
        t0, t1 = win[0]
    else:
        spans = [(s, s + d) for p in dev_names
                 for ev in planes[p].values() for _, s, d in ev]
        if not spans:
            return {"devices": [], "window_s": 0.0, "busy_s": 0.0}
        t0, t1 = min(s for s, _ in spans), max(e for _, e in spans)
    devices, ops, programs = [], {}, {}
    for p in dev_names:
        lines = planes[p]
        op_ev = _clip(lines.get(OPS_LINE, []), t0, t1)
        busy = _union([(s, e) for _, s, e in op_ev])
        devices.append({"name": p, "busy": busy,
                        "busy_s": sum(e - s for s, e in busy) * 1e-9})
        for n, s, e in op_ev:
            n = short_op(n)
            ops[n] = ops.get(n, 0.0) + (e - s) * 1e-9 / len(dev_names)
        for n, s, e in _clip(lines.get(MODULES_LINE, []), t0, t1):
            pr = programs.setdefault(program_name(n),
                                     {"seconds": 0.0, "count": 0})
            pr["seconds"] += (e - s) * 1e-9 / len(dev_names)
            pr["count"] += 1.0 / len(dev_names)
    window_s = (t1 - t0) * 1e-9
    out = {"window_s": window_s,
           "busy_s": sum(d["busy_s"] for d in devices)
           / max(len(devices), 1),
           "devices": [{"name": d["name"], "busy_s": d["busy_s"]}
                       for d in devices],
           "idle_share_worst": max(
               (1.0 - d["busy_s"] / window_s for d in devices), default=None)
           if window_s else None,
           "programs": programs,
           "device_ops": [[n, s] for n, s in sorted(
               ops.items(), key=lambda kv: -kv[1])],
           "idle_gaps": []}
    if devices:
        worst = min(devices, key=lambda d: d["busy_s"])
        out["idle_gaps"] = _gaps_by_host(worst["busy"], host, t0, t1)
    return out


def _gaps_by_host(busy, host, t0, t1, longest: int = 2000):
    """Idle gaps of one device, summed by the host event that overlaps
    each gap most (events as long as half the window excluded): what the
    host was doing while the device waited. Only the `longest` gaps are
    attributed; the rest are summed as `(short gaps)`."""
    edges = [t0] + [x for s, e in busy for x in (s, e)] + [t1]
    gaps = sorted(((edges[i + 1] - edges[i], edges[i], edges[i + 1])
                   for i in range(0, len(edges), 2)
                   if edges[i + 1] > edges[i]), reverse=True)
    # a host event that spans half the window explains no single gap
    hev = sorted((s, s + d, n) for ev in host.values() for n, s, d in ev
                 if 0 < d < 0.5 * (t1 - t0))
    starts = [h[0] for h in hev]
    import bisect
    longest_host = max((e - s for s, e, _ in hev), default=0.0)
    by = {}
    for length, g0, g1 in gaps[:longest]:
        best, best_ov = "(no host event)", 0.0
        i = bisect.bisect_left(starts, g0 - longest_host)
        while i < len(hev) and hev[i][0] < g1:
            s, e, n = hev[i]
            ov = min(e, g1) - max(s, g0)
            if ov > best_ov:
                best, best_ov = n, ov
            i += 1
        by[best] = by.get(best, 0.0) + length * 1e-9
    rest = sum(g[0] for g in gaps[longest:]) * 1e-9
    if rest:
        by["(short gaps)"] = rest
    return [[n, s] for n, s in sorted(by.items(), key=lambda kv: -kv[1])]


def reduce_file(path: str, chips: int = None) -> dict:
    return reduce(load(path, (OPS_LINE, MODULES_LINE)), chips)


# ------------------------------------------------------ tools: inspect, trim

def inspect(path: str) -> None:
    planes = load(path)
    for pname, lines in planes.items():
        print(f"plane {pname!r}")
        for lname, ev in lines.items():
            tot = sum(d for _, _, d in ev) * 1e-9
            names = {}
            for n, _, d in ev:
                names[n] = names.get(n, 0.0) + d * 1e-9
            top = sorted(names.items(), key=lambda kv: -kv[1])[:8]
            print(f"  line {lname!r}: {len(ev)} events, {tot:.4f} s; top: "
                  + "; ".join(f"{n[:60]} {s:.4f}" for n, s in top))


def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        b = n & 0x7F
        n >>= 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _field(num: int, payload) -> bytes:
    if isinstance(payload, int):
        return _varint(num << 3) + _varint(payload)
    if isinstance(payload, str):
        payload = payload.encode()
    return _varint((num << 3) | 2) + _varint(len(payload)) + payload


def encode_xspace(planes: dict) -> bytes:
    """A minimal XSpace (tsl/profiler/protobuf/xplane.proto) holding the
    given planes/lines/events, readable by ProfileData: what `--trim`
    writes, so that a few milliseconds of a real trace fit in the repo."""
    space = b""
    for pid, (pname, lines) in enumerate(planes.items()):
        meta_ids, meta, body = {}, b"", b""
        for lid, (lname, events) in enumerate(lines.items()):
            base = min((s for _, s, _ in events), default=0.0)
            lb = _field(1, lid + 1) + _field(2, lname.split("#")[0]) \
                + _field(3, int(base))
            for n, s, d in events:
                if n not in meta_ids:
                    meta_ids[n] = len(meta_ids) + 1
                    em = _field(1, meta_ids[n]) + _field(2, n)
                    meta += _field(4, _field(1, meta_ids[n]) + _field(2, em))
                ev = _field(1, meta_ids[n]) \
                    + _field(2, int(round((s - base) * 1000))) \
                    + _field(3, int(round(d * 1000)))
                lb += _field(4, ev)
            body += _field(3, lb)
        space += _field(1, _field(1, pid + 1) + _field(2, pname) + body
                        + meta)
    return space


def trim(src: str, dst: str, from_ms: float, to_ms: float) -> None:
    """Keep the device planes' module and op lines and the host's
    `bench.*` annotations, between two offsets from the first device
    event."""
    planes = load(src)
    dev = [p for p in planes if DEVICE_PLANE.match(p)]
    first = min(s for p in dev for ev in planes[p].values()
                for _, s, _ in ev)
    t0, t1 = first + from_ms * 1e6, first + to_ms * 1e6
    keep = {}
    for p in dev:
        keep[p] = {ln: [(n, s, e - s) for n, s, e in _clip(ev, t0, t1)]
                   for ln, ev in planes[p].items()
                   if ln in (OPS_LINE, MODULES_LINE)}
    host = {ln: [(n, s, e - s) for n, s, e in _clip(ev, t0, t1)
                 if n.startswith("bench.")]
            for ln, ev in planes.get(HOST_PLANE, {}).items()}
    keep[HOST_PLANE] = {ln: ev for ln, ev in host.items() if ev}
    with open(dst, "wb") as f:
        f.write(encode_xspace(keep))


def main(argv) -> int:
    if len(argv) >= 2 and argv[0] == "--inspect":
        inspect(argv[1])
        return 0
    if len(argv) >= 3 and argv[0] == "--trim":
        opt = dict(zip(argv[3::2], argv[4::2]))
        trim(argv[1], argv[2], float(opt.get("--from-ms", 0)),
             float(opt.get("--to-ms", 50)))
        return 0
    print(__doc__, file=sys.stderr)
    return 2


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))

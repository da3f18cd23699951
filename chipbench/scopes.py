#!/usr/bin/env python3
"""The program's named scopes of the device ops in a profiler trace.

    python3 chipbench/scopes.py <trace dir>

prints the device seconds of the traced ``bench.window`` per (phase x
sublayer) and per (phase x reversible step), the share no scope covers with
its largest ops, and every idle gap on the device with what the host and
the device's ``XLA Modules`` / ``Steps`` lines were doing in it.

An op's scope path is its HLO ``op_name``, which the profiler keeps as the
``tf_op`` stat of the op's event metadata on the device plane, e.g.
``jit(fused_train_step)/walk.update/.../transpose(jvp(attn))/dot_general:``.
jaxlib's ``ProfileData`` does not expose event-metadata stats, so the
device planes are read here with a small protobuf wire-format reader (field
numbers from tsl's ``xplane.proto``).  An op is under a scope when the scope
is a whole component of that path, bare or inside a transform such as
``jvp(...)`` / ``transpose(...)``.  The scope names are the program's
(``src/repro/obs/scopes.py``); they are restated below because a trace of a
program without them has to read as "no scopes", never as an error.
"""
from __future__ import annotations

import functools
import json
import pathlib
import re
import sys
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent
if __name__ == "__main__":
    sys.path.insert(0, str(ROOT))

from chipbench import tracing  # noqa: E402

TRACE_ROOT = ROOT / ".chipbench_trace"
DEVICE_PLANE = "/device:TPU:"
TF_OP = "tf_op"
#: host spans that may name an idle gap: the training driver's own
HOST_SPANS = {"data", "step_dispatch", "loss_sync"}

PHASES = ("prelude", "walk.forward", "head_loss", "walk.probe",
          "walk.update", "opt.update")
REVERSIBLE = ("rev.invert", "rev.recompute", "rev.vjp")
SUBLAYERS = ("attn", "moe.router", "moe.dispatch", "moe.experts",
             "moe.combine", "moe.shared", "mlp", "norm")
PROGRAM = frozenset(PHASES + REVERSIBLE + SUBLAYERS)
#: scopes outside the layers that a step's time may fall under
STEP_PARTS = frozenset(SUBLAYERS + ("opt.update", "head_loss", "prelude"))


# ------------------------------------------------------------ wire format
#
# XSpace.planes 1; XPlane: name 2, lines 3, event_metadata 4 (map entry:
# key 1, value 2), stat_metadata 5 (map entry: key 1, value 2);
# XEventMetadata: id 1, name 2, stats 5; XStat: metadata_id 1,
# str_value 5, ref_value 7; XStatMetadata: id 1, name 2.

def _varint(buf, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf, start: int, end: int):
    """(field number, value) of one message: an int for varints, the
    (start, end) offsets of the bytes for length-delimited fields."""
    i = start
    while i < end:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            v = (i, i + n)
            i += n
        elif wire == 1:
            v, i = None, i + 8
        elif wire == 5:
            v, i = None, i + 4
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield key >> 3, v


def _text(buf, span) -> str:
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def _map_value(buf, span):
    """(key, value span) of a map entry."""
    key = val = None
    for f, v in _fields(buf, *span):
        if f == 1:
            key = v
        elif f == 2:
            val = v
    return key, val


def _plane_tf_ops(buf, start: int, end: int) -> Optional[Dict[str, str]]:
    """{event-metadata name: tf_op} of a device plane, None for another
    plane."""
    name, metas, stat_names = None, [], {}
    for f, v in _fields(buf, start, end):
        if f == 2:
            name = _text(buf, v)
            if not name.startswith(DEVICE_PLANE):
                return None
        elif f == 4:
            metas.append(v)
        elif f == 5:
            _, val = _map_value(buf, v)
            if val is not None:
                sid = sname = None
                for g, w in _fields(buf, *val):
                    if g == 1:
                        sid = w
                    elif g == 2:
                        sname = _text(buf, w)
                stat_names[sid] = sname
    if name is None:
        return None
    tf_id = next((k for k, n in stat_names.items() if n == TF_OP), None)
    out: Dict[str, str] = {}
    if tf_id is None:
        return out
    for span in metas:
        _, val = _map_value(buf, span)
        if val is None:
            continue
        ev_name, tf_op = None, None
        for g, w in _fields(buf, *val):
            if g == 2:
                ev_name = _text(buf, w)
            elif g == 5:
                sid, value = None, None
                for h, x in _fields(buf, *w):
                    if h == 1:
                        sid = x
                    elif h == 5:
                        value = _text(buf, x)
                    elif h == 7:
                        value = stat_names.get(x)
                if sid == tf_id and value:
                    tf_op = value
        if ev_name and tf_op and ev_name not in out:
            out[ev_name] = tf_op
    return out


@functools.lru_cache(maxsize=8)
def tf_ops(path: str) -> Dict[str, str]:
    """{HLO text of a device op (``tracing.Op.name``): its tf_op}, over
    every device plane of the ``.xplane.pb`` at ``path``."""
    with open(path, "rb") as f:
        buf = memoryview(f.read())
    out: Dict[str, str] = {}
    for field, span in _fields(buf, 0, len(buf)):
        if field != 1:
            continue
        plane = _plane_tf_ops(buf, *span)
        for k, v in (plane or {}).items():
            out.setdefault(k, v)
    return out


@functools.lru_cache(maxsize=None)
def scope_path(tf_op: str) -> Tuple[str, ...]:
    """The names on an op's scope path, outermost first, transforms
    unwrapped: ``a/transpose(jvp(attn))/dot_general:`` ->
    ``('a', 'transpose', 'jvp', 'attn')``; the op itself is left out."""
    path = tf_op.rpartition(":")[0] if ":" in tf_op else tf_op
    names: List[str] = []
    for comp in path.split("/")[:-1]:
        names.extend(t for t in re.split(r"[()]", comp) if t)
    return tuple(names)


# ------------------------------------------------------------ attribution

def _union_s(ops, window) -> float:
    iv = tracing._clip(tracing._union([(o.start_ns, o.end_ns) for o in ops]),
                       *window)
    return sum(e - s for s, e in iv) * 1e-9


def tagged_ops(summary, path: str) -> List[Tuple[object, frozenset]]:
    """Chip 0's ops in the window, each with the program scopes on its
    path."""
    m = tf_ops(path)
    ops = summary.ops[0] if summary.ops else []
    return [(o, PROGRAM.intersection(scope_path(m.get(o.name, ""))))
            for o in ops]


def trace_of(ctx) -> Optional[str]:
    """The ``.xplane.pb`` of the running cell: the workload of
    ``BENCHMARK.json`` whose configuration and traffic are the context's."""
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    name = (ctx.get("config") or {}).get("name")
    for w in spec["workloads"]:
        traffic = HERE / "traffic" / f"{w['traffic']}.json"
        if (w["config"] != name or not traffic.exists()
                or json.loads(traffic.read_text()) != ctx.get("traffic")):
            continue
        try:
            return tracing.find_xplane(TRACE_ROOT / w["name"])
        except FileNotFoundError:
            return None
    return None


def share(ctx, names: Iterable[str]) -> Optional[float]:
    """Percent of the window's device-busy time (chip 0, loop ops left out)
    in which an op under any of ``names`` ran; None off a training cell,
    without a trace, or when no op in the window carries a program scope."""
    if ctx.get("kind") != "train":
        return None
    path = trace_of(ctx)
    if path is None:
        return None
    t = ctx["trace"]
    tagged = tagged_ops(t, path)
    if not any(sc for _, sc in tagged):
        return None
    busy = _union_s([o for o, _ in tagged], t.window)
    if busy <= 0:
        return None
    want = set(names)
    return 100.0 * _union_s([o for o, sc in tagged if sc & want],
                            t.window) / busy


# ------------------------------------------------------------ report

def _first(path: Sequence[str], among: Sequence[str]) -> Optional[str]:
    return next((n for n in path if n in among), None)


def _last(path: Sequence[str], among: Sequence[str]) -> Optional[str]:
    return next((n for n in reversed(path) if n in among), None)


def breakdown(summary, path: str) -> dict:
    """Device seconds of the window by (phase, sublayer) and by (phase,
    reversible step): a phase is the outermost phase scope on an op's path,
    a reversible step the outermost ``rev.*``, a sublayer the innermost
    sublayer scope."""
    m = tf_ops(path)
    w0, w1 = summary.window
    by_sub: Dict[Tuple[str, str], float] = {}
    by_rev: Dict[Tuple[str, str], float] = {}
    loose: Dict[str, float] = {}
    no_part: Dict[str, float] = {}
    total = in_phase = in_part = 0.0
    for o in summary.ops[0] if summary.ops else []:
        s = (min(o.end_ns, w1) - max(o.start_ns, w0)) * 1e-9
        if s <= 0:
            continue
        p = scope_path(m.get(o.name, ""))
        phase = _first(p, PHASES) or "-"
        sub = _last(p, SUBLAYERS) or "-"
        rev = _first(p, REVERSIBLE) or "-"
        total += s
        by_sub[phase, sub] = by_sub.get((phase, sub), 0.0) + s
        by_rev[phase, rev] = by_rev.get((phase, rev), 0.0) + s
        if phase == "-":
            loose[o.label] = loose.get(o.label, 0.0) + s
        else:
            in_phase += s
        if STEP_PARTS.intersection(p):
            in_part += s
        else:
            no_part[o.label] = no_part.get(o.label, 0.0) + s
    top = lambda d: sorted(d.items(), key=lambda kv: -kv[1])[:5]  # noqa: E731
    return {"device_s": total, "busy_s": summary.busy_s,
            "window_s": summary.window_s,
            "phase_share": in_phase / total if total else 0.0,
            "part_share": in_part / total if total else 0.0,
            "by_sublayer": {f"{a} | {b}": v for (a, b), v in by_sub.items()},
            "by_reversible": {f"{a} | {b}": v for (a, b), v in by_rev.items()},
            "no_phase_top": top(loose), "no_part_top": top(no_part)}


def device_gaps(path: str, summary, min_s: float = 1e-3) -> List[dict]:
    """Idle gaps of chip 0 longer than ``min_s``: the host span over the
    gap's middle, and whether the device's ``XLA Modules`` line had a
    module running then (ops missing from ``XLA Ops``) or not (the device
    idle), with the ``Steps`` line's step."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    lines: Dict[str, list] = {}
    planes = sorted((p for p in pd.planes if p.name.startswith(DEVICE_PLANE)),
                    key=lambda p: p.name)
    for line in planes[0].lines if planes else []:
        if line.name in ("XLA Modules", "Steps"):
            lines[line.name] = [(ev.name, int(ev.start_ns),
                                 int(ev.start_ns + ev.duration_ns))
                                for ev in line.events]

    def over(name, a, b):
        return [(n, max(s, a), min(e, b)) for n, s, e in lines.get(name, [])
                if e > a and s < b]

    out = []
    for a, b in summary.gaps():
        if (b - a) * 1e-9 < min_s:
            continue
        mods = over("XLA Modules", a, b)
        out.append({"start_s": (a - summary.window[0]) * 1e-9,
                    "dur_s": (b - a) * 1e-9,
                    "host": summary.label((a + b) // 2),
                    "module_s": sum(e - s for _, s, e in mods) * 1e-9,
                    "modules": sorted({n for n, _, _ in mods}),
                    "steps": sorted({n for n, _, _ in over("Steps", a, b)})})
    return out


def _table(cells: Dict[str, float], rows: Sequence[str], cols: Sequence[str],
           total: float) -> str:
    grid: Dict[Tuple[str, str], float] = {}
    for k, v in cells.items():
        r, c = k.split(" | ")
        grid[r, c] = v
    rows = [r for r in rows if any((r, c) in grid for c in cols)]
    cols = [c for c in cols if any((r, c) in grid for r in rows)]
    w = max(12, *(len(c) + 1 for c in cols))
    out = [f"{'':14s}" + "".join(f"{c:>{w}s}" for c in cols)
           + f"{'all':>{w}s}{'share':>8s}"]
    for r in rows:
        vals = [grid.get((r, c), 0.0) for c in cols]
        out.append(f"{r:14s}" + "".join(f"{v:{w}.4f}" for v in vals)
                   + f"{sum(vals):{w}.4f}{100 * sum(vals) / total:7.2f}%")
    return "\n".join(out)


def report(trace_dir) -> dict:
    import time
    path = tracing.find_xplane(trace_dir)
    summary = tracing.load(path, chips=1, span_names=HOST_SPANS)
    t0 = time.perf_counter()
    tf_ops.cache_clear()
    tf_ops(path)
    parse_s = time.perf_counter() - t0
    b = breakdown(summary, path)
    total = b["device_s"] or 1.0
    phases = PHASES + ("-",)
    print(f"trace {path}\nwindow {b['window_s']:.4f} s, device busy "
          f"{b['busy_s']:.4f} s, op time {b['device_s']:.4f} s; scopes "
          f"read in {parse_s:.3f} s")
    print("\nphase x sublayer (device s)")
    print(_table(b["by_sublayer"], phases, SUBLAYERS + ("-",), total))
    print("\nphase x reversible step (device s)")
    print(_table(b["by_reversible"], phases, REVERSIBLE + ("-",), total))
    print(f"\nunder a phase: {100 * b['phase_share']:.2f}%; under a "
          f"sublayer, opt.update, head_loss or prelude: "
          f"{100 * b['part_share']:.2f}%")
    print("largest ops under no phase:")
    for n, s in b["no_phase_top"]:
        print(f"  {s:10.4f} s  {n}")
    print("largest ops under no sublayer, opt.update, head_loss or prelude:")
    for n, s in b["no_part_top"]:
        print(f"  {s:10.4f} s  {n}")
    gaps = device_gaps(path, summary)
    print(f"\nidle gaps over 1 ms: {len(gaps)}")
    for g in gaps:
        print(f"  at {g['start_s']:.4f} s, {g['dur_s'] * 1e3:.3f} ms, host "
              f"{g['host']}, modules running {g['module_s'] * 1e3:.3f} ms "
              f"{g['modules']} steps {g['steps']}")
    b.update(gaps=gaps, parse_s=parse_s)
    print(json.dumps(b))
    return b


if __name__ == "__main__":
    if len(sys.argv) != 2:
        sys.exit(__doc__.split("\n\n")[1])
    report(sys.argv[1])

"""Reduction of a profiler trace (``.xplane.pb``) to the numbers the
per-layer metrics read.

* The window is the host span ``bench.window`` that the drivers open.
* Device busy time is the union of the intervals in which an operation ran
  on a chip's ``XLA Ops`` line, clipped to the window, averaged over chips.
* Idle gaps are the complements of that union inside the window; each is
  named by the innermost benchmark span on the host that covers its middle
  (``data``, ``step_dispatch``, ``loss_sync``, ``admit``, ``drain``...).
* A device op's event carries its HLO text.  Its opcode is read from it;
  loop and call ops (``while``, ``conditional``, ``call``) enclose their
  bodies' ops and are left out.  A Pallas kernel is a ``tpu_custom_call``
  whose HLO names no kernel, so ``kernel_kind`` tells the kernels apart by
  their operand and result shapes.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

WINDOW_SPAN = "bench.window"
OPS_LINE = "XLA Ops"
CONTAINERS = ("while", "conditional", "call")
_OPCODE = re.compile(r"[\]\}\)]\s+([a-z][a-z0-9-]*)\(")
_SHAPE = re.compile(r"\b(bf16|f32|f16|s32|u32|s8|u8|pred)\[([0-9,]*)\]")


def opcode(text: str) -> str:
    m = _OPCODE.search(text)
    return m.group(1) if m else "?"


def _shapes(part: str):
    return [(t, tuple(int(x) for x in d.split(",") if x))
            for t, d in _SHAPE.findall(part)]


def custom_call_shapes(text: str):
    """(result shapes, operand shapes) of a ``tpu_custom_call`` op."""
    head, _, rest = text.partition(" custom-call(")
    operands = rest.split("), custom_call_target")[0]
    return _shapes(head.partition(" = ")[2]), _shapes(operands)


def kernel_kind(text: str):
    """Which Pallas kernel a ``tpu_custom_call`` is, from its shapes:
    ``gmm`` (s32 tile map, (M, K) rows, (E, K, N) weights), ``flash_fwd``
    (q, k, v in; out and f32 log-sum-exp), ``flash_dq`` (six in, one out),
    ``flash_dkv`` (six in, two bf16 out); None for any other op."""
    if 'custom_call_target="tpu_custom_call"' not in text:
        return None
    outs, ins = custom_call_shapes(text)
    if (len(ins) == 3 and ins[0][0] == "s32" and len(ins[0][1]) == 1
            and len(ins[1][1]) == 2 and len(ins[2][1]) == 3):
        return "gmm"
    f32_last1 = [s for s in ins if s[0] == "f32" and s[1][-1:] == (1,)]
    if len(ins) == 3 and len(outs) == 2 and outs[1][0] == "f32":
        return "flash_fwd"
    if len(ins) == 6 and len(f32_last1) == 2:
        if len(outs) == 1:
            return "flash_dq"
        if len(outs) == 2 and all(o[0] == "bf16" for o in outs):
            return "flash_dkv"
    return "custom"


@dataclasses.dataclass
class Op:
    name: str            # the HLO text of the op
    start_ns: int
    dur_ns: int

    @property
    def end_ns(self):
        return self.start_ns + self.dur_ns

    @property
    def label(self) -> str:
        """A kernel's kind ('flash_fwd'), else the instruction and its
        opcode ('%fusion.12 fusion')."""
        kind = kernel_kind(self.name)
        if kind and kind != "custom":
            return kind
        return f"{self.name.partition(' = ')[0]} {opcode(self.name)}"


@dataclasses.dataclass
class Summary:
    window: Tuple[int, int]
    ops: List[List[Op]]                       # per chip, in the window
    spans: List[Tuple[str, int, int]]         # host spans, ns
    busy_s: float
    window_s: float

    def kernel_ops(self, kinds: Sequence[str]) -> List[Op]:
        """Device ops (all chips) that are one of these kernels."""
        return [o for ops in self.ops for o in ops
                if kernel_kind(o.name) in kinds]

    def gaps(self) -> List[Tuple[int, int]]:
        w0, w1 = self.window
        out = []
        for ops in self.ops[:1]:
            t = w0
            for s, e in _union([(o.start_ns, o.end_ns) for o in ops]):
                if s > t:
                    out.append((t, s))
                t = max(t, e)
            if w1 > t:
                out.append((t, w1))
        return out

    def label(self, t: int) -> str:
        best = None
        for name, s, e in self.spans:
            if name == WINDOW_SPAN or not s <= t <= e:
                continue
            if best is None or e - s < best[2] - best[1]:
                best = (name, s, e)
        return best[0] if best else "other"

    def breakdown(self) -> dict:
        tot: Dict[str, float] = {}
        for o in self.ops[0] if self.ops else []:
            tot[o.label] = tot.get(o.label, 0.0) + o.dur_ns * 1e-9
        top = sorted(tot.items(), key=lambda kv: -kv[1])[:10]
        gaps = sorted(self.gaps(), key=lambda g: g[0] - g[1])[:10]
        return {"device_ops": [[n, s] for n, s in top],
                "idle_gaps": [[self.label((a + b) // 2), (b - a) * 1e-9]
                              for a, b in gaps]}


def _union(iv: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[List[int]] = []
    for s, e in sorted(iv):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(iv, w0, w1):
    return [(max(s, w0), min(e, w1)) for s, e in iv if e > w0 and s < w1]


def find_xplane(trace_dir) -> str:
    files = sorted(glob.glob(os.path.join(str(trace_dir), "**",
                                          "*.xplane.pb"), recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def load(path: str, chips: Optional[int] = None,
         span_names: Optional[set] = None) -> Summary:
    """``span_names``: the host spans that may name an idle gap (the
    benchmark's own); all host events when None."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    spans, dev_lines = [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    dev_lines.append((plane.name, line))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if (span_names is None or ev.name in span_names
                            or ev.name == WINDOW_SPAN):
                        spans.append((ev.name, int(ev.start_ns),
                                      int(ev.start_ns + ev.duration_ns)))
    wins = [(s, e) for n, s, e in spans if n == WINDOW_SPAN]
    if not wins:
        raise ValueError(f"no '{WINDOW_SPAN}' span in {path}")
    w0, w1 = wins[0]
    dev_lines.sort(key=lambda pl: pl[0])
    if chips:
        dev_lines = dev_lines[:chips]
    per_chip, busy = [], []
    for _, line in dev_lines:
        ops = [Op(ev.name, int(ev.start_ns), int(ev.duration_ns))
               for ev in line.events
               if ev.start_ns + ev.duration_ns > w0 and ev.start_ns < w1
               and opcode(ev.name) not in CONTAINERS]
        per_chip.append(ops)
        iv = _clip(_union([(o.start_ns, o.end_ns) for o in ops]), w0, w1)
        busy.append(sum(e - s for s, e in iv) * 1e-9)
    if not per_chip:
        raise ValueError(f"no device '{OPS_LINE}' line in {path}")
    return Summary((w0, w1), per_chip, spans, sum(busy) / len(busy),
                   (w1 - w0) * 1e-9)


def summarize(trace_dir, spans=None, chips: Optional[int] = None) -> Summary:
    names = {n for n, _, _ in spans.items} if spans is not None else None
    return load(find_xplane(trace_dir), chips, names)

"""Reduction of a JAX profiler trace to the benchmark's device numbers.

``record(logdir)`` is a context manager that traces with the Python tracer
off; ``read(logdir, device)`` loads the newest ``.xplane.pb`` below it, and
``reduce(events, ...)`` turns the events into:

* ``window_s`` — the length of the host span ``bench.window``;
* ``busy_s`` — the union of the device's op intervals inside the window,
  averaged over the devices;
* ``op_s`` — device seconds by op, each op named ``<program>:<op>`` after
  the XLA module that ran it;
* ``module_s`` — device seconds by XLA module (jit program);
* ``gaps`` — idle seconds inside the window by what the host was doing: the
  latest-starting ``bench.*`` span around the middle of each gap.

On a TPU the device ops are the lines ``XLA Ops`` (and ``XLA Modules``) of
the planes ``/device:TPU:<n>``.  ``device="cpu"`` reads the CPU client's
thread instead, so that the reduction can be tested without a chip.
"""
from __future__ import annotations

import bisect
import contextlib
import dataclasses
import glob
import os
import re
import shutil

SPAN_PREFIX = "bench."


@dataclasses.dataclass
class Events:
    ops: list[list[tuple[str, int, int]]]  # per device: (name, start_ns, dur_ns)
    modules: list[list[tuple[str, int, int]]]  # per device
    spans: list[tuple[str, int, int]]  # host bench.* spans


@contextlib.contextmanager
def record(logdir: str):
    import jax

    shutil.rmtree(logdir, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(logdir, profiler_options=opts)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def span(name: str):
    """A host span on the profiler's clock (a no-op when nothing traces)."""
    import jax

    return jax.profiler.TraceAnnotation(SPAN_PREFIX + name)


def _program(name: str) -> str:
    return re.sub(r"\(\d+\)$", "", name)


def _op(name: str) -> str:
    """An op's short name: a TPU trace names each op by its whole HLO
    instruction (``%fusion.3 = f32[...] fusion(...)``)."""
    return name.split(" = ", 1)[0].lstrip("%")


def read(logdir: str, device: str = "tpu") -> Events:
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(logdir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no trace under {logdir}")
    pd = ProfileData.from_file(files[-1])
    ops, modules, spans = [], [], []
    for plane in pd.planes:
        if device == "tpu" and plane.name.startswith("/device:TPU:"):
            o, m = [], []
            for line in plane.lines:
                if line.name == "XLA Ops":
                    o = [(_op(e.name), int(e.start_ns), int(e.duration_ns))
                         for e in line.events]
                elif line.name == "XLA Modules":
                    m = [(_program(e.name), int(e.start_ns), int(e.duration_ns))
                         for e in line.events]
            ops.append(o)
            modules.append(m)
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                evs = [(e.name, int(e.start_ns), int(e.duration_ns)) for e in line.events]
                spans += [e for e in evs if e[0].startswith(SPAN_PREFIX)]
                if device == "cpu" and line.name.startswith("tf_XLAPjRtCpuClient"):
                    ops.append([e for e in evs if e[2] > 0
                                and not e[0].startswith("ThreadpoolListener")])
                    modules.append([])
    return Events(ops, modules, spans)


def _union(intervals: list[tuple[int, int]]) -> list[tuple[int, int]]:
    out: list[list[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(iv, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in iv if e > lo and s < hi]


def reduce(ev: Events, window: str = "window") -> dict:
    win = [s for s in ev.spans if s[0] == SPAN_PREFIX + window]
    if not win:
        raise ValueError(f"the trace has no {SPAN_PREFIX}{window} span")
    w0, w1 = win[0][1], win[0][1] + win[0][2]
    busy_ns = []
    op_ns: dict[str, int] = {}
    module_ns: dict[str, int] = {}
    unions = []
    for ops, mods in zip(ev.ops, ev.modules):
        mods = sorted(mods, key=lambda m: m[1])
        starts = [m[1] for m in mods]
        u = _union(_clip([(s, s + d) for _, s, d in ops], w0, w1))
        unions.append(u)
        busy_ns.append(sum(e - s for s, e in u))
        for name, s, d in ops:
            if s + d <= w0 or s >= w1:
                continue
            i = bisect.bisect_right(starts, s) - 1
            prog = mods[i][0] if i >= 0 and s < mods[i][1] + mods[i][2] else ""
            key = f"{prog}:{name}" if prog else name
            op_ns[key] = op_ns.get(key, 0) + d
        for name, s, d in mods:
            if s + d > w0 and s < w1:
                module_ns[name] = module_ns.get(name, 0) + d
    n_dev = max(len(busy_ns), 1)
    gaps: dict[str, int] = {}
    host = sorted((s, s + d, n) for n, s, d in ev.spans if n != SPAN_PREFIX + window)
    host_starts = [h[0] for h in host]
    if unions:
        u = unions[0]
        edges = [w0] + [x for iv in u for x in iv] + [w1]
        for s, e in zip(edges[0::2], edges[1::2]):
            if e <= s:
                continue
            mid = (s + e) // 2
            i = bisect.bisect_right(host_starts, mid)
            # the latest-starting host span that covers the gap's middle
            around = [h for h in host[max(0, i - 16):i] if mid < h[1]]
            label = around[-1][2][len(SPAN_PREFIX):] if around else "other"
            gaps[label] = gaps.get(label, 0) + (e - s)
    return {
        "window_s": (w1 - w0) / 1e9,
        "busy_s": sum(busy_ns) / n_dev / 1e9,
        "op_s": {k: v / 1e9 for k, v in op_ns.items()},
        "module_s": {k: v / 1e9 for k, v in module_ns.items()},
        "gaps": {k: v / 1e9 for k, v in gaps.items()},
        "devices": len(busy_ns),
    }


def top(d: dict, n: int = 10) -> list:
    return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:n]]


def program_seconds(reduced: dict, programs) -> float:
    """Device seconds of the XLA modules whose names start with one of
    ``programs`` (the jit names of the entry points)."""
    return sum(s for name, s in reduced["module_s"].items()
               if any(name.startswith(p) for p in programs))

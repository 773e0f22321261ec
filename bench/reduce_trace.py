"""From a profiler trace to per-layer numbers.

``traced(dir)`` records a window with ``jax.profiler`` (the window is the
host span ``bench.window``), reduces the ``.xplane.pb`` it wrote to a
:class:`TraceSummary`, and deletes the trace.  The summary holds, in the
trace's own clock and clipped to the window:

* the device's operations (the ``XLA Ops`` line of each ``/device:TPU:N``
  plane), their union (busy time) and the idle share;
* each Pallas kernel event's source file.  The event names the HLO
  instruction; the compiled program's text (``attach``) holds, for each
  ``tpu_custom_call``, the Mosaic kernel it runs, whose locations name the
  files its body was written in.  Kernels are so told apart by file even
  where they share a function name (``_kernel``, ``_bwd_kernel``).  The
  HLO's own op metadata cannot serve: inside a loop body it points at the
  loop, not at the ``pallas_call``;
* the host's events, to say what the host was doing in each idle gap.

``CompileCounter`` counts compilations inside a window.
"""
from __future__ import annotations

import base64
import bisect
import contextlib
import dataclasses
import pathlib
import re
import shutil

WINDOW = "bench.window"
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/backend_compile_duration")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
CONTAINERS = ("while", "conditional", "call")
_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
_OPCODE = re.compile(r"[}\])] ([a-z][\w-]*)\(")
_KERNEL_CALL = re.compile(
    r'^\s*(%[\w.-]+) = .*custom_call_target="tpu_custom_call".*?'
    r'"body":"([A-Za-z0-9+/=]+)"', re.M)
_PY_FILE = re.compile(rb"[\w/.-]+\.py")
_SHAPE = re.compile(r"\w+\[([\d,]+)\]")
#: Kernels whose Mosaic bodies name no kernel file (the generated
#: backwards, traced under ``jax.vjp``, keep only their callers'
#: locations), told apart by function name and the largest rank among
#: their results: the NHWC backward writes per-tile patches (rank 6), the
#: rows backward row blocks (rank 2).  A stopgap until every
#: ``pallas_call`` carries a name of its own.
BY_NAME = ((b"_bwd_kernel", 4, "kernels/fused_stack/nhwc_bwd.py"),
           (b"_bwd_kernel", 2, "kernels/fused_stack/rows_bwd.py"))


class CompileCounter:
    """Counts JAX tracing and backend compilation while entered."""

    def __init__(self):
        self.names: list[str] = []

    @property
    def count(self) -> int:
        return len(self.names)

    def _listen(self, name, *_args, **_kw):
        if name in COMPILE_EVENTS:
            self.names.append(name)

    def __enter__(self):
        import jax.monitoring

        jax.monitoring.register_event_duration_secs_listener(self._listen)
        return self

    def __exit__(self, *exc):
        import jax.monitoring

        jax.monitoring.unregister_event_duration_listener(self._listen)
        return False


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start: int          # ns, trace clock
    end: int
    module: str = ""    # the program it ran in (device ops only)

    @property
    def seconds(self) -> float:
        return (self.end - self.start) * 1e-9

    @property
    def instruction(self) -> str:
        return self.name.split(" ", 1)[0]

    @property
    def opcode(self) -> str:
        m = _OPCODE.search(self.name)
        return m.group(1) if m else ""


def kernel_sources(hlo_text: str) -> tuple[str, dict[str, str | None]]:
    """``(module name, {instruction: defining file})`` for every Pallas
    kernel call of a compiled program's HLO text."""
    module = hlo_text.split(",", 1)[0].split()[-1]
    out = {}
    for m in _KERNEL_CALL.finditer(hlo_text):
        body = base64.b64decode(m.group(2))
        files = {f.decode().split("/repro/", 1)[-1]
                 for f in _PY_FILE.findall(body)}
        src = defining_file(files)
        if src is None:
            result = m.group(0).split(" = ", 1)[1].split(" custom-call(")[0]
            rank = max(len(d.split(",")) for d in _SHAPE.findall(result))
            src = next((f for name, least, f in BY_NAME
                        if name in body and rank >= least), None)
        out[m.group(1)] = src
    return module, out


def defining_file(files) -> str | None:
    """The kernel module a Mosaic body was written in, from the files its
    locations name: the one under ``kernels/`` that is not a wrapper
    (``ops.py``) or a twin (``ref.py``).  A backward kernel may reuse its
    forward's helpers, never the reverse, so a ``*_bwd.py`` wins over its
    forward; any other tie is left unattributed."""
    cands = sorted(f for f in files if f.startswith("kernels/")
                   and f.rsplit("/", 1)[-1] not in ("ops.py", "ref.py",
                                                    "__init__.py"))
    if len(cands) == 1:
        return cands[0]
    bwd = [f for f in cands if f.endswith("_bwd.py")]
    return bwd[0] if len(bwd) == 1 else None


def union_seconds(intervals, lo: int, hi: int) -> float:
    """Length of the union of ``(start, end)`` intervals within
    ``[lo, hi]``, in seconds."""
    total, cur_s, cur_e = 0, None, None
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total * 1e-9


def idle_gaps(intervals, lo: int, hi: int) -> list[tuple[int, int]]:
    """The stretches of ``[lo, hi]`` that no interval covers."""
    gaps, t = [], lo
    for s, e in sorted(intervals):
        if s > t:
            gaps.append((t, min(s, hi)))
        t = max(t, e)
        if t >= hi:
            break
    if t < hi:
        gaps.append((t, hi))
    return [(s, e) for s, e in gaps if e > s]


@dataclasses.dataclass
class TraceSummary:
    window: tuple[int, int]
    device_ops: dict[int, list[Event]]      # device id -> its ops
    host: list[Event]
    sources: dict[tuple[str, str], str | None] = dataclasses.field(
        default_factory=dict)               # (module, instruction) -> file

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    @property
    def busy_s(self) -> float:
        """Busy seconds, averaged over the devices."""
        if not self.device_ops:
            return 0.0
        per = [union_seconds([(e.start, e.end) for e in ops], *self.window)
               for ops in self.device_ops.values()]
        return sum(per) / len(per)

    def attach(self, hlo_texts) -> None:
        """Learn the kernels of the compiled programs that ran."""
        for text in hlo_texts:
            module, calls = kernel_sources(text)
            for instr, src in calls.items():
                self.sources[(module, instr)] = src

    def source_file(self, ev: Event) -> str | None:
        return self.sources.get((ev.module, ev.instruction))

    def kernel_events(self, source_suffix: str) -> list[Event]:
        return [e for ops in self.device_ops.values() for e in ops
                if (self.source_file(e) or "").endswith(source_suffix)]

    def kernel_count(self, source_suffix: str) -> int:
        return len(self.kernel_events(source_suffix))

    def kernel_seconds(self, source_suffix: str) -> float:
        return sum(e.seconds for e in self.kernel_events(source_suffix))

    def op_group(self, ev: Event) -> str:
        src = self.source_file(ev)
        if src:
            return "pallas " + src
        return re.sub(r"[.\d]+$", "", ev.instruction.lstrip("%"))

    def breakdown(self, top: int = 10) -> dict:
        """The device operations that took most time (loops and calls,
        which hold other operations, left out), and the longest idle gaps
        named by what the host was doing in each."""
        by: dict[str, float] = {}
        for ops in self.device_ops.values():
            for e in ops:
                if e.opcode not in CONTAINERS:
                    g = self.op_group(e)
                    by[g] = by.get(g, 0.0) + e.seconds
        n_dev = max(len(self.device_ops), 1)
        ops = sorted(((k, v / n_dev) for k, v in by.items()),
                     key=lambda kv: -kv[1])[:top]
        first = next(iter(self.device_ops.values()), [])
        gaps = sorted(idle_gaps([(e.start, e.end) for e in first],
                                *self.window),
                      key=lambda g: g[0] - g[1])[:top]
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[self.host_doing((s + e) // 2), (e - s) * 1e-9]
                              for s, e in gaps if e - s >= 1000]}

    def host_doing(self, t: int) -> str:
        """The innermost host event under way at ``t``."""
        under = [e for e in self.host
                 if e.start <= t < e.end and e.name != WINDOW]
        if not under:
            return "host: no span"
        return "host: " + min(under, key=lambda e: e.end - e.start).name


def compiled_text(jitted, *args) -> str:
    """The optimized HLO text of ``jitted`` at ``args`` (arrays or
    ``ShapeDtypeStruct``s): the same program, found again in the
    compilation cache."""
    return jitted.lower(*args).compile().as_text()


def abstract(tree):
    """``tree`` with each array replaced by its shape, dtype and
    placement."""
    import jax

    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=a.sharding)
        if isinstance(a, jax.Array) else a, tree)


def _in_modules(ops, modules) -> list[Event]:
    """Each op tagged with the program (``XLA Modules`` event) it ran
    in."""
    modules = sorted(modules, key=lambda m: m.start)
    starts = [m.start for m in modules]
    out = []
    for e in ops:
        i = bisect.bisect_right(starts, e.start) - 1
        name = ""
        if i >= 0 and e.end <= modules[i].end:
            name = modules[i].name.split("(", 1)[0]
        out.append(dataclasses.replace(e, module=name))
    return out


def load(path: pathlib.Path) -> TraceSummary:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    device_ops: dict[int, list[Event]] = {}
    host: list[Event] = []
    window = None
    for plane in pd.planes:
        m = _DEVICE_PLANE.match(plane.name)
        if m:
            lines = {ln.name: [Event(ev.name, int(ev.start_ns),
                                     int(ev.end_ns)) for ev in ln.events]
                     for ln in plane.lines
                     if ln.name in (OPS_LINE, MODULES_LINE)}
            device_ops[int(m.group(1))] = _in_modules(
                lines.get(OPS_LINE, []), lines.get(MODULES_LINE, []))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    e = Event(ev.name, int(ev.start_ns), int(ev.end_ns))
                    if e.name == WINDOW:
                        window = (e.start, e.end)
                    host.append(e)
    if window is None:
        raise ValueError(f"no {WINDOW!r} span in {path}")
    lo, hi = window
    device_ops = {d: [e for e in ops if e.end > lo and e.start < hi]
                  for d, ops in device_ops.items()}
    host = [e for e in host if e.end > lo and e.start < hi]
    return TraceSummary(window=window, device_ops=device_ops, host=host)


class _Holder:
    result: TraceSummary | None = None


@contextlib.contextmanager
def traced(out_dir: pathlib.Path):
    """Trace the body as the window; ``.result`` holds the summary once
    the block has exited.  The trace files are deleted after reading."""
    import jax

    out_dir = pathlib.Path(out_dir)
    shutil.rmtree(out_dir, ignore_errors=True)
    out_dir.mkdir(parents=True)
    holder = _Holder()
    # the Python tracer would slow the host loop it is meant to observe
    # (the serve engine's tick is Python), inflating the idle share
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(out_dir), profiler_options=options)
    try:
        with jax.profiler.TraceAnnotation(WINDOW):
            yield holder
    finally:
        jax.profiler.stop_trace()
    files = sorted(out_dir.rglob("*.xplane.pb"))
    if not files:
        raise RuntimeError(f"the profiler wrote no trace under {out_dir}")
    holder.result = load(files[-1])
    shutil.rmtree(out_dir, ignore_errors=True)

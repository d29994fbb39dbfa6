"""Profiler trace of one window, reduced to device busy time and to device
time per ``repro_*`` scope.

``traced`` runs a window under ``jax.profiler`` inside a host span named
``chipbench_window`` and reads the ``.xplane.pb`` it wrote (in a
temporary directory, removed afterwards).  ``reduce`` keeps the device
planes' ``XLA Ops`` events that overlap that span.  On the TPU these
events are named by their HLO instruction's text (``%fusion.12 = ...``)
and nest: a ``while`` spans the ops of its body, and a few ops overlap
the next one.  So every instant of the window is given to the innermost
op running then (the one that started last), which splits the device's
busy time into self times that add up to it.  An op's scope is the
``op_name`` the program's named scopes left on that instruction in the
compiled HLO text the driver hands in.
"""

from __future__ import annotations

import collections
import dataclasses
import glob
import heapq
import os
import re
import shutil
import tempfile

WINDOW_SPAN = "chipbench_window"
_SCOPE = re.compile(r"repro_[a-z0-9_]+")
_INSTRUCTION = re.compile(r"%?([\w.\-]+) = ")


@dataclasses.dataclass
class Op:
  name: str           # HLO instruction, e.g. "fusion.12"
  start_ns: float
  dur_ns: float       # self time: the instants this op was the innermost
  scope: str          # op_name path, "" when unknown
  device: str


def instruction(event_name: str) -> str:
  """``%fusion.12 = f32[...] fusion(...)`` -> ``fusion.12``."""
  m = _INSTRUCTION.match(event_name)
  return m.group(1) if m else event_name


def self_times(spans) -> list[float]:
  """For intervals [(start, end)], the time during which each was the
  innermost (latest started) of those running."""
  points = sorted([(s, 1, i) for i, (s, _) in enumerate(spans)]
                  + [(e, 0, i) for i, (_, e) in enumerate(spans)])
  out = [0.0] * len(spans)
  heap, prev = [], None
  for t, is_start, i in points:
    if prev is not None and t > prev:
      while heap and heap[0][1] <= prev:
        heapq.heappop(heap)
      if heap:
        out[heap[0][2]] += t - prev
    if is_start:
      heapq.heappush(heap, (-spans[i][0], spans[i][1], i))
    prev = t
  return out


def scopes(path: str) -> list[str]:
  """The ``repro_*`` scopes on an op_name path, outermost first."""
  return _SCOPE.findall(path)


def kind(op: Op) -> str:
  """Which soft-operator layer an op belongs to: ``isotonic_bwd`` (the
  VJP's segment algebra, any ``repro_*_bwd_*`` scope), ``isotonic``
  (the forward solve), ``projection`` (sort, permutations, gathers
  under ``repro_projection_*``), or ``""``."""
  sc = scopes(op.scope)
  if any("_bwd_" in s for s in sc):
    return "isotonic_bwd"
  if any(s.startswith("repro_isotonic_") for s in sc):
    return "isotonic"
  if any(s.startswith("repro_projection_") for s in sc):
    return "projection"
  return ""


def _union_ns(intervals) -> float:
  total, end = 0.0, -float("inf")
  for s, e in sorted(intervals):
    if e <= end:
      continue
    total += e - max(s, end)
    end = e
  return total


@dataclasses.dataclass
class Summary:
  window_s: float
  busy_s: float                  # mean over the devices traced
  ops: list                      # Op, clipped to the window
  gaps: list                     # (host activity, seconds), longest first

  def seconds(self, which: str) -> float:
    """Device self seconds of the ops of one ``kind`` (all devices)."""
    return sum(o.dur_ns for o in self.ops if kind(o) == which) * 1e-9

  def breakdown(self) -> dict:
    by = collections.Counter()
    for o in self.ops:
      sc = scopes(o.scope)
      label = (sc[-1] + ":" if sc else "") + re.sub(r"\.\d+$", "", o.name)
      by[label] += o.dur_ns * 1e-9
    return {"device_ops": [[k, v] for k, v in by.most_common(10)],
            "idle_gaps": [[k, v] for k, v in self.gaps[:10]]}


def hlo_op_names(text: str) -> dict[str, str]:
  """instruction -> op_name, from compiled HLO text.  A fusion without
  metadata takes its fused computation's root's."""
  names, calls, roots, comp = {}, {}, {}, None
  for line in text.splitlines():
    head = re.match(r"^(?:ENTRY )?%?([\w.\-]+) .*\{\s*$", line)
    if head and not line.startswith(" "):
      comp = head.group(1)
      continue
    m = re.match(r"\s*(ROOT )?%?([\w.\-]+) = ", line)
    if not m:
      continue
    inst = m.group(2)
    op = re.search(r'op_name="([^"]*)"', line)
    if op:
      names[inst] = op.group(1)
    c = re.search(r"calls=%?([\w.\-]+)", line)
    if c:
      calls[inst] = c.group(1)
    if m.group(1) and comp:
      roots[comp] = names.get(inst, "")
  for inst, called in calls.items():
    if inst not in names and roots.get(called):
      names[inst] = roots[called]
  return names


def reduce(path: str, hlo_texts=(), window_ns=None) -> Summary:
  """Summary of the ``chipbench_window`` span of one xplane file (or of
  ``window_ns`` = (start, end) where given)."""
  import jax
  data = jax.profiler.ProfileData.from_file(path)
  op_names = {}
  for text in hlo_texts:
    op_names.update(hlo_op_names(text))

  window = window_ns
  host = []                              # (start, end, name) host spans
  devices = {}
  for plane in data.planes:
    if plane.name.startswith("/device:TPU"):
      for line in plane.lines:
        if line.name == "XLA Ops":
          devices[plane.name] = [(e.start_ns, e.start_ns + e.duration_ns,
                                  e.name) for e in line.events]
    elif plane.name.startswith("/host:"):
      for line in plane.lines:
        for e in line.events:
          if e.name == WINDOW_SPAN and window_ns is None:
            window = (e.start_ns, e.start_ns + e.duration_ns)
          elif e.duration_ns > 0 and not e.name.startswith("$"):
            host.append((e.start_ns, e.start_ns + e.duration_ns, e.name))
  if window is None:
    raise ValueError(f"no {WINDOW_SPAN} span in {path}")
  w0, w1 = window

  ops, busy = [], []
  for dev, events in devices.items():
    inside = [(max(s, w0), min(e, w1), name) for s, e, name in events
              if min(e, w1) > max(s, w0)]
    selfs = self_times([(s, e) for s, e, _ in inside])
    for (s, _, name), t in zip(inside, selfs):
      inst = instruction(name)
      ops.append(Op(inst, s, t, op_names.get(inst, ""), dev))
    busy.append(sum(selfs))
  busy_s = (sum(busy) / len(busy)) * 1e-9 if busy else 0.0
  return Summary(window_s=(w1 - w0) * 1e-9, busy_s=busy_s, ops=ops,
                 gaps=_idle_gaps(devices, host, w0, w1))


def _idle_gaps(devices, host, w0, w1):
  """The ten longest idle stretches of the first device inside the
  window, longest first, each named by the innermost host span around
  its middle."""
  first = sorted((s, e) for s, e, _ in next(iter(devices.values()), []))
  gaps, end = [], w0
  for s, e in first + [(w1, w1)]:
    if s > end:
      gaps.append((end, min(s, w1)))
    end = max(end, e)
  out = []
  for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:10]:
    mid = (s + e) / 2
    inside = [(hs, he, n) for hs, he, n in host if hs <= mid <= he]
    name = min(inside, key=lambda h: h[1] - h[0])[2] if inside else "none"
    out.append((name, (e - s) * 1e-9))
  return out


def traced(window_fn, seconds: float, hlo_texts=lambda: ()):
  """Run ``window_fn(seconds)`` under the profiler; (Summary, Window)."""
  import jax
  tmp = tempfile.mkdtemp(prefix="chipbench_trace_")
  options = jax.profiler.ProfileOptions()
  options.python_tracer_level = 0    # Python calls cost the host too much
  options.enable_hlo_proto = False
  try:
    jax.profiler.start_trace(tmp, profiler_options=options)
    try:
      with jax.profiler.TraceAnnotation(WINDOW_SPAN):
        window = window_fn(seconds)
    finally:
      jax.profiler.stop_trace()
    files = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"), recursive=True)
    return reduce(files[0], hlo_texts()), window
  finally:
    shutil.rmtree(tmp, ignore_errors=True)

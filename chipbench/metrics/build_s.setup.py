"""Seconds the program spent building the step program the window runs:
the sum of its own ``build_s`` records (``repro.obs.tracing``: one for
each outermost trace, lower and backend compile, a compile served by the
persistent cache counting its load).  In the train loop those are the
records made inside the ``repro_train_step`` span, the train step's
build in the first check step; the benchmark's own jits (weights, norms,
the HLO text after a traced window, the float32 reference) build outside
it.  The loss loops build nothing else, so every record counts.  None
where the program keeps no such records."""

import re

PHASES = ("trace", "lower", "compile")
_LABEL = re.compile(r"(\w+)=([^,}]*)")


def seconds(record, phases=PHASES):
  from repro.obs import metrics
  records = metrics.histograms("build_s{")
  if record.traffic["driver"] == "train_loop":
    records = {k: h for k, h in records.items()
               if dict(_LABEL.findall(k)).get("span") == "repro_train_step"}
  if not records:
    return None
  return sum(h["sum"] for k, h in records.items()
             if dict(_LABEL.findall(k)).get("phase") in phases)


def read(record):
  return seconds(record)

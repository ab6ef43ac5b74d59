"""The work counters kept one a file in perf/counters/, handed to perf/work.py
under their names before the benchmark's tests run, as a generator's `setup`
hands its configuration's counter to the harness for a run: perf/run.py and
perf/tests/test_work.py look a counter up on that module alone, and a PR that
brings a configuration may not edit either (ROADMAP S6 makes this plain)."""

import glob
import importlib.util
import os
import sys

PERF = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, PERF)

import work  # noqa: E402

for path in sorted(glob.glob(os.path.join(PERF, "counters", "*.py"))):
    name = os.path.splitext(os.path.basename(path))[0]
    spec = importlib.util.spec_from_file_location("perf_counters_" + name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    setattr(work, name, getattr(module, name))

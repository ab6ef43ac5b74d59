"""The plain reference of a sparse path put in the place of the program's
`FitFleet`, sound or broken: `perf/faults_fleet.py`'s stand-in and faults
(members reversed, the final update left out, `reg` left out), and one that
only a column plan could make: `dictionary_ids_shifted`, one column of few
distinct ids (a dictionary in the program's plan) read one id further on, in
every member. Used by perf/probe_fleet_sparse.py, on the chip at the cell's
own size, to read the control and the faults against the limits. The
benchmark's own runs never load this file.
"""

from __future__ import annotations

import importlib.util
import os

# the column whose ids the fault shifts: the first categorical field of a
# Criteo row (1,460 categories), after the 13 integer fields
SHIFTED = 13


def _sibling(name: str):
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), name + ".py")
    spec = importlib.util.spec_from_file_location("perf_" + name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


_FLEET = _sibling("faults_fleet")


def dictionary_ids_shifted(reference, arrays, data, params, precision):
    return reference.fit(arrays, data, params, precision=precision, shifted_column=SHIFTED)[0]


FAULTS = dict(_FLEET.FAULTS, dictionary_ids_shifted=dictionary_ids_shifted)
_FLEET.FAULTS.update(FAULTS)  # this file's own copy of the dense path's faults
planted = _FLEET.planted

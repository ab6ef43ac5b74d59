"""Blocking host-device synchronisations a fit, from the program's own
counter `iteration.host_sync` over the window. (`readback.count` ticks for
the same packed readback, so it is not added.) Repeats exactly."""


def read(run):
    attempted = run["window"]["attempted"]
    if not attempted:
        return None
    return run["counters"].get("iteration.host_sync", 0) / attempted

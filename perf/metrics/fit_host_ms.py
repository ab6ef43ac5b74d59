"""Mean fit wall minus mean device-busy time inside a fit, both from the
traced window: what the host adds to a fit around the device's work."""


def read(run):
    trace = run["trace"]
    if trace is None or not trace["spans"]:
        return None
    return (trace["span_s"] - trace["busy_in_spans_s"]) / trace["spans"] * 1000.0

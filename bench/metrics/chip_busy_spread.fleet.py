"""The busiest chip's busy seconds in the traced window over the mean of
the chips the cell used (bench/trace.py's per-chip `busy_s`). 1.0 is an
even spread; n on n chips means one chip did all the work. A chip whose
plane held no op line is absent from `trace.devices` and counts as 0."""


def read(rec):
    t = rec.get("trace")
    if not t or not t.get("devices"):
        return None
    v = [d["busy_s"] for d in t["devices"]]
    n = max(len(v), int(rec.get("device", {}).get("count", 0)))
    mean = sum(v) / n
    return None if mean <= 0 else max(v) / mean

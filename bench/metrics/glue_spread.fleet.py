"""The chip with most non-kernel op time in the traced window over the
mean of the chips the cell used (bench/trace.py's per-chip `glue_s`).
Near 1 when each chip runs its own weight re-stacks and copies; near n
on n chips when one chip runs every worker's. A chip whose plane held no
op line is absent from `trace.devices` and counts as 0."""


def read(rec):
    t = rec.get("trace")
    if not t or not t.get("devices"):
        return None
    v = [d["glue_s"] for d in t["devices"]]
    n = max(len(v), int(rec.get("device", {}).get("count", 0)))
    mean = sum(v) / n
    return None if mean <= 0 else max(v) / mean

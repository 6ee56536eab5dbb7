"""Mean rows per stacked launch in the window, from the micro-batchers'
request and launch counters."""


def read(rec):
    if not rec.get("launches"):
        return None
    return rec["launch_rows"] / rec["launches"]

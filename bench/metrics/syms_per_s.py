"""syms_per_s: equalized symbols that reached the host in the window, over
the window's length (first step's start to last step's end)."""


def read(rec):
    if not rec.get("symbols") or not rec.get("window_s"):
        return None
    return rec["symbols"] / rec["window_s"]

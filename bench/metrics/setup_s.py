"""setup_s: seconds from process start to the window's start (imports,
chip start-up, inputs and weights, engines, warm-up and compiles)."""


def read(rec):
    return rec.get("setup_s")

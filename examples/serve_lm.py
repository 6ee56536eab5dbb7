"""Serve a small LM with batched requests through the production serving
path (prefill + donated-state greedy decode) — reduced qwen3 config on CPU;
the same code path serves the full configs on a pod (launch/serve.py).

    PYTHONPATH=src python examples/serve_lm.py [--arch qwen3-0.6b]
"""
import argparse

from repro.compile_cache import enable_compile_cache
from repro.launch import serve


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-0.6b")
    args = ap.parse_args()
    serve.main(["--arch", args.arch, "--batch", "4",
                "--prompt-len", "64", "--gen", "24"])


if __name__ == "__main__":
    main()

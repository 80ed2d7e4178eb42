"""UQ serving driver — the paper's deployment shape.

Starts an UM-Bridge HTTP server exposing one built-in model (L2-Sea
analogue, composite ROM, tsunami, or an LM wrapped as a UQ model); compiled
programs persist in the compile cache (`repro.launch.compile_cache`):

    PYTHONPATH=src python -m repro.launch.serve --model l2sea --port 4242

then from any UM-Bridge client (Python/MATLAB/R/...):

    model = umbridge.HTTPModel("http://localhost:4242", "forward")
    model([[0.3, -6.0, 0, ..., 0]])
"""
from __future__ import annotations

import argparse

import jax

from repro.core.server import serve_models
from repro.launch.compile_cache import enable_compile_cache


def build_model(name: str, arch: str, reduced: bool):
    if name == "l2sea":
        from repro.apps.l2sea import L2SeaModel

        return L2SeaModel()
    if name == "composite":
        from repro.apps.composite import CompositeModel

        return CompositeModel()
    if name == "tsunami":
        from repro.apps.tsunami import TsunamiModel

        return TsunamiModel()
    if name == "lm":
        from repro.apps.lm_model import LMUQModel

        return LMUQModel(arch, reduced=reduced)
    raise ValueError(name)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="l2sea", choices=["l2sea", "composite", "tsunami", "lm"])
    ap.add_argument("--arch", default="qwen3-0.6b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--port", type=int, default=4242)
    args = ap.parse_args()

    enable_compile_cache()
    model = build_model(args.model, args.arch, args.reduced)
    print(f"serving '{model.name}' on http://0.0.0.0:{args.port} "
          f"(devices: {len(jax.devices())})")
    serve_models([model], args.port)


if __name__ == "__main__":
    main()

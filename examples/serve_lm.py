"""Serve a small LM with batched requests through the slot engine.

    PYTHONPATH=src python examples/serve_lm.py
"""

from repro.core.runtime import use_compile_cache
from repro.launch.serve import main as serve_main

if __name__ == "__main__":
    use_compile_cache()
    serve_main(["--arch", "qwen3-0.6b", "--smoke", "--requests", "6",
                "--max-new", "12", "--batch", "3"])

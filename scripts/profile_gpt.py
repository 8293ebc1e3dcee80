"""Thin wrapper: per-module + per-op profile of the GPT bench step.

The round-4 one-off this script used to be is now the ``profile``
subcommand of the monitor CLI (``python -m apex_tpu.monitor profile``,
docs/perf.md "Profiling your model"): analytic per-module attribution
by default, ``--per-op`` for the XProf per-op table this script
originally printed. This wrapper pins the GPT bench shapes.

Usage (from the repo root): PYTHONPATH=. python scripts/profile_gpt.py
"""
import sys

from apex_tpu.monitor.__main__ import main

if __name__ == "__main__":
    sys.exit(main([
        "profile", "--model", "gpt", "--batch", "8", "--seq", "1024",
        "--hidden", "1024", "--layers", "12", "--heads", "16",
        "--vocab", "32768", "--dtype", "bfloat16",
        "--attention", "flash", "--fused-lm-head", "--per-op",
        *sys.argv[1:],
    ]))

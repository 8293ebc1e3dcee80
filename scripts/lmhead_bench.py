"""Thin wrapper over the autotune CLI (PR 8) — the fused LM-head CE
tile sweep that used to live here (the r5 fused-vs-unfused root-cause
probe with its hand-listed ``(bt, bv)`` grid) is now ONE sweep
implementation in ``apex_tpu.tune``:

    python -m apex_tpu.ops tune --kernel lm_head_ce \\
        --shapes "n=8192,v=32768,h=1024,dtype=bf16" \\
        --shapes "n=16384,v=30522,h=768,dtype=bf16"

This wrapper runs exactly that (the GPT and BERT bench shapes) and
writes the persistent per-device cache that
``fused_lm_head_cross_entropy(block_t=None, ...)`` resolves from. The
historical sweep numbers are quoted in
``ops/lm_head_ce.py:_pick_blocks``. Extra arguments pass through.
"""
import sys

from apex_tpu.ops.__main__ import main

_DEFAULTS = ["tune", "--kernel", "lm_head_ce"]
if not any(a.startswith("--shapes") for a in sys.argv[1:]):
    _DEFAULTS += ["--shapes", "n=8192,v=32768,h=1024,dtype=bf16",
                  "--shapes", "n=16384,v=30522,h=768,dtype=bf16"]

if __name__ == "__main__":
    sys.exit(main(_DEFAULTS + sys.argv[1:]))

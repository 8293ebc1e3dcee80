"""Timed sweep harness: measure candidate configs, rank, pick.

Design constraints (ISSUE 8 tentpole b):

- **compile excluded** — each candidate's runner is built and warmed
  before its first timed call, so compile time never pollutes the
  ranking (it is recorded separately as ``build_s``);
- **median-of-k steady state** — every timed call is also recorded
  through the monitor timer path (``tune/sweep/<label>`` timer events),
  so a sweep leaves the same JSONL evidence as a bench section;
- **per-config timeout** — one pathological compile (or a config
  Mosaic rejects only at the end of a long pipeline) cannot eat the
  sweep: the config is marked failed and the sweep moves on;
- **injectable timer** — ``timer(fn, config) -> seconds`` replaces the
  wall clock. Tests and the bench smoke section inject a deterministic
  fake clock (a pure function of the config), making cache resolution,
  ranking, and persistence testable on CPU without a TPU: same grid +
  same fake timings => same chosen config, bit for bit.

Determinism: ranking is ``min`` over medians with ties broken by
candidate order (the generator emits coarsest-first), via a stable sort
on ``(median, index)``.
"""

from __future__ import annotations

import signal
import threading
import time
from typing import Callable, Optional

from apex_tpu.monitor import hooks


class SweepTimeout(Exception):
    """A candidate exceeded its per-config budget."""


def wall_timer(fn: Callable[[], None], config: dict) -> float:
    """Default timer: run ``fn`` once, return elapsed seconds."""
    t0 = time.perf_counter()
    fn()
    return time.perf_counter() - t0


def _call_with_timeout(fn: Callable[[], object],
                       timeout_s: Optional[float]):
    """Run ``fn`` under a SIGALRM budget when one is available (main
    thread, a positive budget); otherwise run it unguarded. SIGALRM is
    the only way to interrupt a native XLA compile; worker threads fall
    back to unguarded calls — the sweep still skips the config on any
    exception, it just cannot preempt a hang there.

    ITIMER_REAL is process-global, so an enclosing alarm budget (an
    outer SIGALRM the caller armed) is suspended for the duration and
    re-armed with its REMAINING time afterwards — if it would have
    expired while ours was live, it fires (almost) immediately under
    its restored handler instead of being silently cancelled."""
    if (timeout_s is None or timeout_s <= 0
            or threading.current_thread() is not threading.main_thread()):
        return fn()

    def _alarm(signum, frame):
        raise SweepTimeout(f"config exceeded {timeout_s:.1f}s budget")

    prev_handler = signal.signal(signal.SIGALRM, _alarm)
    prev_remaining, prev_interval = signal.getitimer(signal.ITIMER_REAL)
    t0 = time.monotonic()
    signal.setitimer(signal.ITIMER_REAL, timeout_s)
    try:
        return fn()
    finally:
        # handler first, then re-arm: an already-due outer budget must
        # fire under ITS handler, not ours
        signal.signal(signal.SIGALRM, prev_handler)
        if prev_remaining > 0:
            elapsed = time.monotonic() - t0
            signal.setitimer(signal.ITIMER_REAL,
                             max(prev_remaining - elapsed, 1e-6),
                             prev_interval)
        else:
            signal.setitimer(signal.ITIMER_REAL, 0.0)


def sweep(candidates: list[dict], build: Callable[[dict], Callable[[], None]],
          *, timer: Optional[Callable[[Callable[[], None], dict], float]]
          = None, median_of: int = 5, warmup: int = 1,
          config_timeout_s: Optional[float] = None,
          label: str = "sweep") -> dict:
    """Measure every candidate, return the ranked result.

    ``build(config)`` returns a zero-arg callable running ONE steady-
    state iteration (it must block until the work is done, e.g. via
    ``jax.block_until_ready``); build + ``warmup`` calls happen before
    timing, so compilation is excluded. ``timer(fn, config)`` returns
    seconds for one iteration (default: wall clock).

    Returns ``{"best": config|None, "best_s": float|None,
    "results": [...], "failed": [...]}`` where each result row is
    ``{config, median_s, timings_s, build_s}`` (results sorted
    best-first) and each failed row is ``{config, error}``.
    """
    timer = timer or wall_timer
    results, failed = [], []
    for idx, config in enumerate(candidates):
        try:
            t_build0 = time.perf_counter()

            def _prepare(config=config):
                fn = build(config)
                for _ in range(max(0, warmup)):
                    fn()
                return fn

            fn = _call_with_timeout(_prepare, config_timeout_s)
            build_s = time.perf_counter() - t_build0
            timings = []
            for _ in range(max(1, median_of)):
                s = _call_with_timeout(
                    lambda: timer(fn, config), config_timeout_s)
                s = float(s)
                timings.append(s)
                hooks.timer_event(f"tune/sweep/{label}", s, config=config)
            timings_sorted = sorted(timings)
            median = timings_sorted[len(timings_sorted) // 2]
            results.append({"config": dict(config), "median_s": median,
                            "timings_s": timings, "build_s": build_s,
                            "_idx": idx})
        except Exception as e:      # a failed config is data; BaseException
            # control-flow (KeyboardInterrupt, SystemExit, a caller's
            # timeout raised from an outer SIGALRM handler) must
            # propagate out of the sweep
            failed.append({"config": dict(config),
                           "error": f"{type(e).__name__}: {e}"})
            hooks.counter("tune/sweep_config_failed")
    results.sort(key=lambda r: (r["median_s"], r["_idx"]))
    for r in results:
        del r["_idx"]
    best = results[0] if results else None
    return {"best": best["config"] if best else None,
            "best_s": best["median_s"] if best else None,
            "results": results, "failed": failed}

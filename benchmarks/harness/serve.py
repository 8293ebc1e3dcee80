"""The measured loop of a serve cell: one thread drives the engine and
plays the clients.

The harness times requests itself, on one clock read after every engine
round: a token sampled in a round gets that round's end as its time. TTFT
counts from the moment the request was due (closed loop: the client's last
completion; open loop: the arrival schedule) to its first token; an
inter-token gap is the time between consecutive tokens of one sequence, so
a prefill that runs ahead of the decode batch is inside the gaps of every
running sequence. Both go on the ``serve-window`` detail line with their
sample counts; the runner's one end-to-end number is the rate of tokens
completed, under the name the traffic file gives it (``rate_metric``). A
tail becomes a metric when a cell's window holds the samples for it
(PERF.md section 7).

A closed loop's first wave is admitted, and prefilled, in set-up, each
request with what is left of it at a random moment of the steady state
(``loadgen.residual_levels``), so that the window opens at full occupancy
and in the mix's steady state, not with every client at token 0. A client
whose request ended inside set-up all the same sends its next one as the
window opens: the loop keeps all its clients.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Dict, Optional

from . import loadgen
from .stats import summary


@dataclasses.dataclass
class ServeProgram:
    engine: Any                    # add_request / step / seqs / sched
    vocab: int                     # token ids are drawn below this
    check: Callable[[], dict]      # vs the plain reference
    attention: dict                # kernel shapes for the roofline
    programs: dict                 # {"prefill": regex of its XLA module}
    info: dict


@dataclasses.dataclass
class _Req:
    sid: int
    due: float                     # when the request was due
    n_out: int
    in_window: bool
    seen: int = 0
    last_t: float = 0.0


def run_serve(prog: ServeProgram, traffic: dict, seed: int, seconds: float,
              tracer, log) -> dict:
    eng = prog.engine
    stream = loadgen.RequestStream(traffic, seed, prog.vocab)
    arrivals = loadgen.Arrivals(traffic["arrival"], seed)
    live: Dict[int, _Req] = {}
    refused = 0

    def issue(due: float, in_window: bool):
        nonlocal refused
        prompt, n_out = stream.next(residual=not in_window)
        try:
            sid = eng.add_request(prompt, n_out)
        except (ValueError, RuntimeError) as e:
            refused += 1
            log("refused", error=repr(e), prompt_tokens=len(prompt),
                max_new_tokens=n_out)
            return
        live[sid] = _Req(sid, due, n_out, in_window)

    # -- set-up: the first wave is admitted (and prefilled) before the window
    t_wave = time.perf_counter()
    for _ in range(arrivals.first_wave()):
        issue(t_wave, in_window=False)
    rounds = 0
    while eng.sched.waiting:
        eng.step()
        rounds += 1
    now = time.perf_counter()
    for r in live.values():
        seq = eng.seqs[r.sid]
        r.seen, r.last_t = seq.num_generated, now
    ended = [r for r in live.values() if eng.seqs[r.sid].done]
    log("first-wave", requests=len(live), rounds=rounds,
        seconds=now - t_wave, running=len(eng.sched.running),
        ended_in_setup=len(ended))

    trace_seconds = float(traffic.get("trace_seconds", 3.0))
    trace_at = seconds / 3.0
    ttft, gaps, lateness = [], [], []
    completed = short = issued_in_window = 0
    steps = []                  # (t_end, n_running, context_tokens)
    traced = None
    steps0 = len(eng.decode_step_times)
    tok0 = eng.tokens_generated
    tracer.mark("window-start")
    t_setup_end = time.perf_counter()
    t0 = t_setup_end
    for r in ended:
        del live[r.sid]
        nxt = arrivals.on_complete(t0)
        if nxt is not None:
            issue(nxt, in_window=True)
            issued_in_window += 1
    while True:
        now = time.perf_counter()
        if tracer.on and not tracer.done:
            if not tracer.active and now - t0 >= trace_at:
                tracer.start()
                traced = {"t_start": time.perf_counter() - t0,
                          "step_lo": len(steps)}
            elif tracer.active and \
                    now - t0 >= traced["t_start"] + trace_seconds:
                tracer.stop()
                traced["t_stop"] = time.perf_counter() - t0
                traced["step_hi"] = len(steps)
        with tracer.annotate("admit"):
            now = time.perf_counter()
            for due in arrivals.due_by(now - t0):
                lateness.append(now - t0 - due)
                issue(t0 + due, in_window=True)
                issued_in_window += 1
            if not eng.sched.has_work:          # open loop, idle server
                nxt = arrivals.next_due()
                if nxt is not None:
                    time.sleep(max(0.0, min(t0 + nxt - time.perf_counter(),
                                            t0 + seconds - now)))
        with tracer.annotate("serve-step"):
            if eng.sched.has_work:
                eng.step()
        now = time.perf_counter()
        with tracer.annotate("admit"):
            if tracer.on:
                running = eng.sched.running
                steps.append((now - t0, len(running),
                              sum(s.num_tokens for s in running)))
            done = []
            for r in live.values():
                seq = eng.seqs[r.sid]
                g = seq.num_generated
                if g > r.seen:
                    if r.seen == 0:
                        if r.in_window:
                            ttft.append(now - r.due)
                    elif r.last_t >= t0:
                        gaps.append(now - r.last_t)
                    r.seen, r.last_t = g, now
                    if seq.done:
                        done.append(r)
            for r in done:
                del live[r.sid]
                completed += 1
                short += r.seen != r.n_out
                nxt = arrivals.on_complete(now)
                if nxt is not None:
                    issue(nxt, in_window=True)
                    issued_in_window += 1
        if now - t0 >= seconds:
            break
    window = now - t0
    tracer.mark("window-end")
    if tracer.active:               # the window ended inside the session
        tracer.stop()
        traced["t_stop"] = window
        traced["step_hi"] = len(steps)
    tokens = eng.tokens_generated - tok0
    failed = refused + short
    decode_times = list(eng.decode_step_times[steps0:])
    if traced is not None:
        sub = steps[traced["step_lo"]:traced["step_hi"]]
        traced["rounds"] = len(sub)
        traced["context_tokens"] = sum(c for _, _, c in sub)
        traced["batch_rows"] = sum(n for _, n, _ in sub)

    log("serve-window", window_s=window, tokens=tokens,
        requests_issued_in_window=issued_in_window, completed=completed,
        refused=refused, short=short, in_flight_at_end=len(live),
        ttft_ms=_ms(summary(ttft, (50, 90, 99))),
        itl_ms=_ms(summary(gaps, (50, 90, 99))),
        decode_steps=len(decode_times),
        generator_lateness_ms=_ms(summary(lateness, (50, 99)))
        if lateness else None,
        preemptions=sum(s.n_preemptions for s in eng.seqs.values()))
    return {
        "kind": "serve", "window_s": window, "t_setup_end": t_setup_end,
        "tokens": tokens, "attempted": issued_in_window, "failed": failed,
        "correct": failed == 0 and completed > 0, "traced": traced,
        "decode_step_times": decode_times,
        "end_to_end": {traffic["rate_metric"]: tokens / window},
    }


def _ms(s: Optional[dict]) -> Optional[dict]:
    if not s:
        return s
    return {k: (1e3 * v if k[0] in "pm" else v) for k, v in s.items()}

"""The measured loop of a train cell.

Steps are dispatched back to back in groups of ``fetch_every``; the loss of
a group's last step is fetched (a training loop that logs), and that is
where the clock is read. Two keys of the traffic file say how:

``groups_in_flight`` (1): how many groups are dispatched before the host
    waits for the oldest one's loss. At 1 the host waits for the group it
    has just dispatched, so the device stands idle after every fetch until
    the next step is dispatched, for as long as the host takes. At 2 the
    next group is already queued when the host waits, as in a loop that
    logs the loss one group late: a host that is held up for less than a
    group's time costs the device nothing. The window then opens as a
    lead-in group completes, and whatever is in flight when it closes is
    awaited and left out.
``rate_from`` ("window"): ``"window"`` is the tokens of all steps completed
    in the window over its wall time. ``"median_group"`` is a group's tokens
    over the MEDIAN time between two fetches: every group is the same work,
    so the median is the rate of the undisturbed loop, and a fetch that the
    host saw late lengthens one reading and shortens the next. It hides
    whatever slows fewer than half the groups, so a mix with periodic work
    (a save every N steps) takes ``"window"``.

The window is a whole number of groups and ends with the first fetch at or
after ``seconds``. Every step's loss stays on the device until the window
is over. The runner's one end-to-end number is the rate of tokens trained,
under the name the traffic file gives it (``rate_metric``); both readings of
the rate are on the ``train-window`` line.
"""

from __future__ import annotations

import collections
import math
import time

from .stats import median


def run_train(prog, traffic: dict, seed: int, seconds: float, tracer,
              log) -> dict:
    """``seed`` is the program's already (weights and the ring); the
    argument keeps the runners' signatures alike."""
    import jax

    fetch_every = int(traffic["fetch_every"])
    ring = prog.batches
    state = prog.state
    prog.state = None                      # donated: no second reference

    # warm-up: the compiled step runs, the loss scale settles
    losses_pre = []
    k = 0
    for _ in range(int(traffic["warmup_steps"])):
        state, loss = prog.step(state, ring[k % len(ring)])
        k += 1
        losses_pre.append(float(loss))
    applied0 = prog.applied_steps(state)
    log("warm-up", steps=len(losses_pre), losses=losses_pre,
        applied_steps=applied0, loss_scale=prog.loss_scale(state))

    lead = int(traffic.get("groups_in_flight", 1)) - 1
    rate_from = traffic.get("rate_from", "window")
    if lead < 0 or rate_from not in ("window", "median_group"):
        raise ValueError(f"groups_in_flight {lead + 1}, rate_from "
                         f"{rate_from!r}: see harness/train.py")
    dispatched = []                        # every step's loss, on the device
    in_flight = collections.deque()        # the last loss of each open group

    def dispatch():
        """``fetch_every`` steps back to back."""
        nonlocal state, k
        for _ in range(fetch_every):
            with tracer.annotate("dispatch"):
                state, loss = prog.step(state, ring[k % len(ring)])
            k += 1
            dispatched.append(loss)
        in_flight.append(loss)

    def fetch():
        """Waits for the oldest open group's loss; returns the time."""
        with tracer.annotate("fetch-loss"):
            jax.block_until_ready(in_flight.popleft())
        return time.perf_counter()

    def drain():
        while in_flight:
            fetch()

    for _ in range(lead):
        dispatch()
    if lead:                    # opens as the lead-in group completes
        dispatch()
        stamps = [fetch()]
    else:                       # opens now, on a device the last fetch idled
        stamps = [time.perf_counter()]
    tracer.mark("window-start")
    i0 = len(dispatched) - lead * fetch_every
    while stamps[-1] - stamps[0] < seconds:
        dispatch()
        stamps.append(fetch())
    drain()                     # in flight at the close: left out
    t_setup_end = stamps[0]
    window = stamps[-1] - stamps[0]
    tracer.mark("window-end")
    group_s = [b - a for a, b in zip(stamps, stamps[1:])]
    i1 = i0 + len(group_s) * fetch_every
    n_window_end = len(dispatched)

    # a traced run: the profiler session over a few more steady steps, AFTER
    # the window, so that starting and stopping it (seconds, on four chips)
    # costs the window nothing and the window is the untraced run's
    traced = None
    if tracer.on:
        trace_steps = int(traffic.get("trace_steps", 4))
        trace_groups = max(1, -(-trace_steps // fetch_every))
        ta = time.perf_counter()
        tracer.start()
        tb = time.perf_counter()
        for _ in range(trace_groups):
            dispatch()
            if len(in_flight) > lead:
                fetch()
        drain()
        tc = time.perf_counter()
        tracer.stop()
        traced = {"steps": len(dispatched) - n_window_end,
                  "seconds": tc - tb,
                  "session_s": time.perf_counter() - ta}
    dispatched = [float(x) for x in jax.device_get(dispatched)]
    losses = dispatched[i0:i1]
    steps = len(losses)
    tokens = steps * prog.tokens_per_step
    rates = {"window": tokens / window,
             "median_group": fetch_every * prog.tokens_per_step
             / median(group_s)}

    # -- what the window has to show -------------------------------------------
    ln_v = math.log(prog.n_classes)
    first = losses_pre[0] if losses_pre else losses[0]
    half = max(1, min(len(ring), steps // 2))
    head = sum(losses[:half]) / half
    tail = sum(losses[-half:]) / half
    # the optimizer's step counter advances only on steps it applied
    skipped = len(dispatched) - (prog.applied_steps(state) - applied0)
    verdict = {
        "losses_finite": all(math.isfinite(x)
                             for x in losses_pre + dispatched),
        "first_loss_near_ln_classes": abs(first - ln_v) <= 0.05 * ln_v,
        "loss_falls": tail < head,
        "no_step_skipped": skipped == 0,
    }
    log("train-window", steps=steps, window_s=window, first_loss=first,
        ln_classes=ln_v, loss_head_mean=head, loss_tail_mean=tail,
        loss_every_8th=losses[::8][:64], loss_scale=prog.loss_scale(state),
        skipped_steps=skipped, steps_dispatched=len(dispatched),
        groups=len(group_s), groups_in_flight=lead + 1,
        group_s_median=median(group_s), group_s_min=min(group_s),
        group_s_max=max(group_s), rate_from=rate_from, rates=rates,
        verdict=verdict)
    return {
        "kind": "train", "window_s": window, "t_setup_end": t_setup_end,
        "steps": steps, "tokens": tokens,
        "attempted": steps, "failed": skipped,
        "correct": all(verdict.values()), "verdict": verdict,
        "traced": traced,
        "end_to_end": {traffic["rate_metric"]: rates[rate_from]},
    }

